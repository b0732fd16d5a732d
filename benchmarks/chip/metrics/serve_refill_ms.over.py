"""``serve_refill_ms``, read in the cell above the knee, where the server's
capacity and not its tail is judged: there it moves ``adapt_per_s``."""

from chipbench.harness import load_module

read = load_module("metrics", "serve_refill_ms").read
