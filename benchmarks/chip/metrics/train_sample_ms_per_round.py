"""Host sampling per federated round: the self time of the round
engine's ``engine.sample`` spans in the traced window (the sampling
policy's client data; the benchmark makes a call's data in bulk inside
its first one), over the rounds of the window's ``engine.dispatch``
spans."""

from chipbench import spans


def read(run, out, summary):
    return spans.per_round_ms(spans.of(summary), "engine.sample")
