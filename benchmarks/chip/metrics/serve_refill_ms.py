"""Median host time of the serving tick's admission: the self time of
the adaptation server's ``serve.refill`` spans in the traced window
(the refill arrays built from the queue's head)."""

from chipbench import spans


def read(run, out, summary):
    return spans.median_ms(spans.of(summary), "serve.refill")
