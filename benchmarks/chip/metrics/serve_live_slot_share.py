"""Share of the serving tick's slot-steps that do real work: over the
adaptation server's ``serve.tick`` spans in the traced window, the sum
of their ``live`` counts (per slot in flight, the least of the tick's
steps and the steps it has left) over the sum of their ``computed``
counts (slots x steps per tick)."""

from chipbench import spans


def read(run, out, summary):
    program = spans.of(summary)
    if program is None:
        return None
    computed = sum(program.values("serve.tick", "computed"))
    if not computed:
        return None
    return 100.0 * sum(program.values("serve.tick", "live")) / computed
