"""Host time of a ``run_federated`` call outside its phases: the mean
self time of the round engine's ``engine.run`` spans that lie wholly in
the traced window (argument checks, set-up of the call's state and
runner, the bills and the result; its block phases are its children)."""

import numpy as np

from chipbench import spans


def read(run, out, summary):
    program = spans.of(summary)
    if program is None:
        return None
    w0, w1 = program.window
    own = [s.own for s in program.named("engine.run")
           if w0 < s.start and s.end < w1]
    return 1e-6 * float(np.mean(own)) if own else None
