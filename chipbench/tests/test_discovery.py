"""A configuration, a traffic mix with an arrival process of its own, a
per-layer metric and a cell added as new files plus entries in a copy
of the benchmark are found by name, with no edit to any file that was
there."""
import json
import shutil

from chipbench import traffic as T
from chipbench.cell import BENCH, ROOT, arrivals, load_cell, reader

#: An arrival process that sends requests two at a time, one pair a
#: second, written as a later change would add one.
PAIRS = """def admit_max(mix, prompt_max):
    return prompt_max


class Arrivals:
    closed = False

    def __init__(self, traffic):
        self.traffic = traffic

    def schedule(self):
        n = 2 * int(self.traffic.seconds)
        sizes = self.traffic.sizes(n)
        return [self.traffic.request(sizes, i, arrival_s=float(i // 2))
                for i in range(n)]

    def after(self, completion):
        return []
"""


def test_new_files_and_entries_are_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}

    b = tmp_path / "chipbench"
    cfg = json.loads((BENCH / "tests" / "data" / "tiny-gqa.json")
                     .read_text())
    cfg["name"] = "tiny-new"
    (b / "configs" / "tiny-new.json").write_text(json.dumps(cfg))
    (b / "arrivals" / "pairs.py").write_text(PAIRS)
    (b / "traffic" / "burst-new.json").write_text(json.dumps(
        {"loop": "pairs",
         "prompt": [{"uniform": {}, "min": 8, "max": 16}],
         "output": {"uniform": {}, "min": 4, "max": 8}}))
    (b / "limits" / "tiny-new.burst.json").write_text('{"greedy_gap": 0.1}')
    (b / "metrics" / "queue_wait_ms.new.py").write_text(
        "def read(rec):\n    return 42.0\n")
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-new", "source": "a test",
                         "file": "chipbench/configs/tiny-new.json",
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "tiny-new.burst", "config": "tiny-new",
                           "traffic": "burst-new", "chips": 1,
                           "why": "a test"})
    m["per_layer"].append({"name": "queue_wait_ms.new", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "scheduler", "moves": "itl_p95_ms",
                           "workloads": ["tiny-new.burst"]})
    m["end_to_end"][[e["name"] for e in m["end_to_end"]]
                    .index("itl_p95_ms")]["workloads"].append(
                        "tiny-new.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    cell = load_cell("tiny-new.burst", root=tmp_path)
    assert cell.config["name"] == "tiny-new"
    process = arrivals(cell.mix["loop"], bench=b)
    reqs = process.Arrivals(T.Traffic(cell.mix, 50, 7, 3.0)).schedule()
    assert [r.arrival_s for r in reqs] == [0.0, 0.0, 1.0, 1.0, 2.0, 2.0]
    assert all(8 <= len(r.prompt) <= 16 for r in reqs)
    assert not process.Arrivals.closed and process.admit_max(cell.mix, 16) == 16
    assert [x["name"] for x in cell.per_layer] == ["queue_wait_ms.new"]
    assert {x["name"] for x in cell.end_to_end} == {"itl_p95_ms",
                                                    "setup_s"}
    assert reader("queue_wait_ms.new", bench=b)(None) == 42.0
    changed = [p for p, data in before.items() if p.read_bytes() != data]
    assert changed == [tmp_path / "BENCHMARK.json"]
