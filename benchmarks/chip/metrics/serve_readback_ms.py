"""Median host time of the serving tick's reads back: the adaptation
server's ``serve.readback`` spans in the traced window (``finished``,
and where a slot finished the query losses and step counts, which wait
for the tick on the device)."""

from chipbench import spans


def read(run, out, summary):
    return spans.median_ms(spans.of(summary), "serve.readback")
