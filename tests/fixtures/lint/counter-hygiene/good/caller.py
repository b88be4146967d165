"""counter-hygiene fixture call sites: literals and f-string families."""

from .utils.observability import EVENTS, HIST


def work(route):
    EVENTS.record("a.b")
    EVENTS.record(f"keyed.{route}")
    HIST.observe("h.a", 0.1)
    HIST.observe(f"hkeyed.{route}", 0.1)
    with HIST.span("h.spanned"):  # a span observes its family
        pass
