"""Share of chip 0's idle time in the traced window that none of the
round engine's phase spans (plan, sample, stage, wait, dispatch, fetch,
eval, checkpoint) covers, in a training cell: the idle time the program's
spans cannot explain."""

from chipbench import spans


def read(run, out, summary):
    return spans.idle_unnamed_share(spans.of(summary), "engine.",
                                    spans.TRAIN_LEAVES)
