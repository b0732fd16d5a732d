"""Host staging per federated round: the self time of the round engine's
``engine.stage`` spans in the traced window (padding, the client
schedule, the transfer to the device), over the rounds of the window's
``engine.dispatch`` spans."""

from chipbench import spans


def read(run, out, summary):
    return spans.per_round_ms(spans.of(summary), "engine.stage")
