"""counter-hygiene fixture groups: declared vocabularies cover every site."""


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


EVENTS = EventCounters(declared=(
    "a.b",
    "keyed.*",  # f-string family: keyed.<route>
))

HIST = LatencyHistograms(declared=(
    "h.a",
    "h.spanned",  # observed through span() only
    "hkeyed.*",  # f-string family: hkeyed.<route>
))
