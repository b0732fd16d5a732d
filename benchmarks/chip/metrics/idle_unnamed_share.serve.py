"""Share of chip 0's idle time in the traced window that none of the
serving tick's phase spans (refill, dispatch, readback, retire) covers,
in the cell below the knee: the idle time the program's spans cannot
explain, among it the load generator's submits between ticks."""

from chipbench import spans


def read(run, out, summary):
    return spans.idle_unnamed_share(spans.of(summary), "serve.",
                                    spans.SERVE_LEAVES)
