"""Closed loop: ``clients`` callers, each sending its next request as
soon as its last one completes.

Mix parameters: ``clients`` and the optional ``preroll_s``.  The first
requests are caught mid-flight: what each has already generated is in
its prompt (``traffic.Traffic.closed_streams``), so an admission can see
a prompt of up to the longest prompt plus all but one output token.
"""


def admit_max(mix: dict, prompt_max: int) -> int:
    """The longest prompt an admission can see."""
    return prompt_max + mix["output"]["max"] - 1


class Arrivals:
    closed = True

    def __init__(self, traffic):
        self._stream = traffic.closed_streams()
        self._clients = traffic.mix["clients"]
        self._start = -float(traffic.mix.get("preroll_s", 0.0))

    def schedule(self):
        """Every caller's first request, due when the pre-roll starts."""
        out = [next(self._stream) for _ in range(self._clients)]
        for req in out:
            req.arrival_s = self._start
        return out

    def after(self, completion):
        """The completed request's caller sends its next one."""
        return [next(self._stream)]
