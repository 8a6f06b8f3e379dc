"""Open loop: every request arrives on its own clock, whether or not the
engine keeps up.

Mix parameters: ``rate`` (requests per second) and the optional
``preroll_s``.  Arrivals are Poisson: the gaps are quantiles of an
exponential, one set for the pre-roll and one for the window, shuffled
by the seed (``traffic.Traffic.open_arrivals``).
"""


def admit_max(mix: dict, prompt_max: int) -> int:
    """The longest prompt an admission can see."""
    return prompt_max


class Arrivals:
    closed = False

    def __init__(self, traffic):
        self._traffic = traffic

    def schedule(self):
        """Every request scheduled in [-preroll_s, seconds), in arrival
        order."""
        return self._traffic.open_arrivals()

    def after(self, completion):
        return []
