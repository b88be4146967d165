"""counter-hygiene fixture call sites: covered and typo'd, counters + hists."""

from .utils.observability import BETA_EVENTS, DELTA_HIST


def work():
    BETA_EVENTS.record("a.b")
    BETA_EVENTS.record("a.typo")  # not covered by declared= patterns
    DELTA_HIST.observe("h.a", 0.1)
    DELTA_HIST.observe("h.typo", 0.1)  # not covered by declared= patterns
    with DELTA_HIST.span("h.span_typo"):  # span literals are checked alike
        pass
