"""Median host time of the serving tick's dispatch: the adaptation
server's ``serve.dispatch`` spans in the traced window (the jitted tick
call with its transfers of the refill to the device)."""

from chipbench import spans


def read(run, out, summary):
    return spans.median_ms(spans.of(summary), "serve.dispatch")
