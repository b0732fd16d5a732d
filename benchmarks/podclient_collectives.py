"""Cohort vs pod-client federated schedules on the 2x16x16 mesh.

The paper's claim, at pod scale: TinyReptile's serial/interpolation
schema needs O(1) cross-client exchanges per round, while a synchronous
cohort all-reduces gradients every inner step. Here: clients = pods.
We lower both steps (probe mode, L=1, K=2) and split the collective
bytes into intra-pod vs cross-pod by parsing replica_groups.

Run in a fresh process (needs 512 host devices):
  PYTHONPATH=src python -m benchmarks.podclient_collectives
"""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")

import json  # noqa: E402
import re  # noqa: E402


def measure():
    import dataclasses
    import jax
    from repro.configs import get_arch, get_shape
    from repro.core.federated import make_pod_client_meta_step
    from repro.launch import specs as specs_mod
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.runtime import steps as steps_lib
    from repro.runtime.flags import probe_scope
    from repro.runtime.shardctx import mesh_context

    mesh = make_production_mesh(multi_pod=True)
    cfg = dataclasses.replace(get_arch("tinyllama-1.1b"), num_layers=1,
                              dtype="float32")
    shape = get_shape("train_4k")
    model = build_model(cfg)

    import numpy as np

    def groups_cross_pod(line, half=256):
        """True iff any replica group mixes devices < half and >= half.
        Handles explicit {{...}} lists and iota [G,S]<=[dims]T(perm)."""
        g = re.search(r"replica_groups=(\{\{.*?\}\}|\[[^ ]*)", line)
        if not g:
            return False  # no groups = all devices = crosses pods
        txt = g.group(1)
        if txt.startswith("{{"):
            for b in re.findall(r"\{([\d,]+)\}", txt):
                ds = [int(x) for x in b.split(",") if x]
                if ds and (min(ds) < half <= max(ds)):
                    return True
            return False
        m = re.match(r"\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", txt)
        if not m:
            return True  # unknown format: conservative
        G, S = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        ids = ids.reshape(G, S)
        return bool(((ids.min(1) < half) & (ids.max(1) >= half)).any())

    def coll_split(hlo):
        intra = cross = 0
        for line in hlo.splitlines():
            m = re.search(
                r"=\s*(.*?)\s+(all-gather|all-reduce|reduce-scatter|"
                r"all-to-all|collective-permute)\(", line)
            if not m:
                continue
            nbytes = 0
            for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
                sz = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4,
                      "pred": 1}.get(dt)
                if sz is None:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * sz
            if "collective-permute" in line:
                # permutes list source_target_pairs instead
                st = re.search(r"source_target_pairs=\{(.*?)\}\s*(,|$)", line)
                is_cross = True
                if st:
                    pairs = re.findall(r"\{(\d+),(\d+)\}", st.group(0))
                    is_cross = any((int(a) < 256) != (int(b) < 256)
                                   for a, b in pairs)
            else:
                is_cross = groups_cross_pod(line)
            if is_cross:
                cross += nbytes
            else:
                intra += nbytes
        return intra, cross

    out = {}
    with probe_scope(True), mesh_context(mesh):
        params = specs_mod.param_specs(cfg, mesh)
        batch = specs_mod.train_batch_specs(cfg, shape, mesh, k_inner=2)
        cohort = steps_lib.make_meta_train_step(model)
        hlo = jax.jit(cohort).lower(params, batch).compile().as_text()
        out["cohort"] = coll_split(hlo)
        pod = make_pod_client_meta_step(model, mesh)
        hlo = jax.jit(pod).lower(params, batch).compile().as_text()
        out["pod_client"] = coll_split(hlo)
    return out


def run():
    """``benchmarks.run`` entry: a 512-host-device CPU compile, run in a
    child process because the device count is fixed at backend init.
    CPU parents only (a child cannot reach a chip its parent holds); a
    failed child raises."""
    import subprocess
    import sys

    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError("podclient_collectives compiles for 512 forced "
                           "host devices in a child process; run it from a "
                           "CPU parent (JAX_PLATFORMS=cpu)")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-m",
                        "benchmarks.podclient_collectives"],
                       capture_output=True, text=True, env=env, timeout=2400)
    if r.returncode != 0:
        raise RuntimeError(f"podclient child failed:\n{r.stderr[-2000:]}")
    d = json.loads(r.stdout.strip().splitlines()[-1])
    return [(f"podclient/{mode}", 0.0,
             f"intra_pod={intra/1e6:.1f}MB cross_pod={cross/1e6:.1f}MB")
            for mode, (intra, cross) in d.items()]


if __name__ == "__main__":
    print(json.dumps(measure()))
