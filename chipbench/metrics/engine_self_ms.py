"""Host milliseconds per engine step in the engine's own code: the self
time of the program's ``engine.*`` spans (step, grow, admit, decode,
finish) over the window, per ``engine.step``.  None where the program
keeps no span counters."""
from chipbench.program_spans import self_ms_per


def read(rec):
    w = rec.window
    engine = [k[len("self_s:"):] for k in w.stats1
              if k.startswith("self_s:engine.")]
    return self_ms_per(w, engine, per="engine.step")
