"""counter-hygiene fixture groups: one undeclared, one with a stale name."""


class EventCounters:
    def __init__(self, declared=None):
        self.declared = tuple(declared or ())

    def record(self, event, n=1):
        pass


class LatencyHistograms:
    def __init__(self, declared=None, buckets=()):
        self.declared = tuple(declared or ())

    def observe(self, name, seconds):
        pass

    def span(self, name, **args):
        pass


ALPHA_EVENTS = EventCounters()  # no declared= vocabulary

BETA_EVENTS = EventCounters(declared=(
    "a.b",
    "stale.name",  # declared but never recorded anywhere
))

GAMMA_HIST = LatencyHistograms()  # no declared= vocabulary

DELTA_HIST = LatencyHistograms(declared=(
    "h.a",
    "stale.hist",  # declared but never observed anywhere
))
