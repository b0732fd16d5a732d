import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks device count on first init.

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch, get_shape, ALL_ARCHS, SHAPES  # noqa: E402
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,  # noqa: E402
                               make_production_mesh)
from repro.launch.specs import input_specs, K_INNER  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import steps as steps_lib  # noqa: E402
from repro.runtime.shardctx import mesh_context  # noqa: E402

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def skip_reason(cfg, shape):
    if shape.name == "long_500k":
        if cfg.name == "whisper-tiny":
            return "enc-dec decoder (448-pos design); 500k decode meaningless"
        if not cfg.supports_long_context():
            return "pure full-attention arch; no sub-quadratic variant"
    return None


def parse_collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op, per kind."""
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(.*?)\s+(" + "|".join(COLLECTIVES) + r")\(", line)
        if not m:
            continue
        lhs, kind = m.group(1), m.group(2)
        if "-start" in line and kind + "-start" not in line:
            pass
        nbytes = 0
        for dt, dims in shape_re.findall(lhs):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] += nbytes
        counts[kind] += 1
    # ignore the paired *-done ops (they repeat the shape): heuristic — the
    # async pairs appear as kind-start/kind-done custom calls in some
    # lowerings; plain HLO here uses synchronous ops, so no dedup needed.
    return out, counts


def build_step_and_args(cfg, shape, mesh, step_kind):
    model = build_model(cfg)
    params, batch = input_specs(cfg, shape, mesh)
    if shape.kind == "train":
        if step_kind == "joint":
            from repro.optim import adamw, constant
            opt = adamw()
            step = steps_lib.make_joint_train_step(model, opt, constant(1e-4))
            opt_state = jax.eval_shape(lambda p: opt.init(p), params)
            opt_step = jax.ShapeDtypeStruct((), np.int32)
            # flatten microbatch dim for joint baseline: (K*mb, S)
            def flat(s):
                return jax.ShapeDtypeStruct((s.shape[0] * s.shape[1],)
                                            + s.shape[2:], s.dtype)
            jbatch = jax.tree.map(flat, batch)
            return step, (params, opt_state, opt_step, jbatch)
        step = steps_lib.make_meta_train_step(model)
        return step, (params, batch)
    if shape.kind == "prefill":
        return steps_lib.make_prefill_step(model), (params, batch)
    return steps_lib.make_decode_step(model), (params, batch)


def _probe_period(cfg):
    """Layer-count granularity for cost probes."""
    if cfg.hybrid_attn_every:
        return cfg.hybrid_attn_every
    from repro.models.transformer import find_period, layer_specs
    return find_period(layer_specs(cfg))


def _compile_cost(cfg, shape, mesh, step_kind, k_inner=None):
    """Probe compile (unrolled) -> dict of numeric costs.

    Probes run in UNIFORM f32 and report bytes/2: the CPU backend inserts
    f32 conversion buffers around bf16 dots (a TPU MXU would not), so a
    bf16 probe overstates HBM traffic; an all-f32 program has no converts
    and is byte-for-byte 2x an ideal bf16 one. FLOP counts are unaffected.
    """
    import dataclasses
    from repro.launch import specs as specs_mod
    from repro.runtime.flags import probe_scope
    cfg = dataclasses.replace(cfg, dtype="float32")
    with probe_scope(True), mesh_context(mesh):
        model = build_model(cfg)
        if shape.kind == "train":
            params, batch = (specs_mod.param_specs(cfg, mesh),
                             specs_mod.train_batch_specs(
                                 cfg, shape, mesh,
                                 k_inner=k_inner or specs_mod.K_INNER))
            step = steps_lib.make_meta_train_step(model)
        elif shape.kind == "prefill":
            params, batch = specs_mod.input_specs(cfg, shape, mesh)
            step = steps_lib.make_prefill_step(model)
        else:
            params, batch = specs_mod.input_specs(cfg, shape, mesh)
            step = steps_lib.make_decode_step(model)
        compiled = jax.jit(step).lower(params, batch).compile()
    out = {}
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    out["flops"] = float(cost.get("flops", 0.0))
    out["bytes"] = float(cost.get("bytes accessed", 0.0)) / 2  # f32 -> bf16
    cb, cc = parse_collective_bytes(compiled.as_text())
    for k in COLLECTIVES:
        out[f"coll_bytes/{k}"] = float(cb[k]) / 2               # f32 -> bf16
        out[f"coll_count/{k}"] = float(cc[k])
    return out


def probe_costs(cfg, shape, mesh, step_kind):
    """Extrapolate exact per-step costs from unrolled probe compiles.

    Model: cost(L, K) = K * (a + b*L) + m   (train; K = inner stream)
           cost(L)    = c0 + b*L            (prefill / decode)
    """
    import dataclasses
    from repro.launch.specs import K_INNER
    p = _probe_period(cfg)
    L_full = cfg.num_layers
    L1, L2 = p, 2 * p
    if L_full <= L2:  # tiny model: probe exactly
        c = _compile_cost(dataclasses.replace(cfg, num_layers=L_full),
                          shape, mesh, step_kind,
                          k_inner=1 if shape.kind == "train" else None)
        if shape.kind != "train":
            return c, {"probes": [L_full]}
        c2 = _compile_cost(dataclasses.replace(cfg, num_layers=L_full),
                           shape, mesh, step_kind, k_inner=2)
        full = {k: c[k] + (c2[k] - c[k]) * (K_INNER - 1) for k in c}
        return full, {"probes": [(L_full, 1), (L_full, 2)]}
    cfg1 = dataclasses.replace(cfg, num_layers=L1)
    cfg2 = dataclasses.replace(cfg, num_layers=L2)
    if shape.kind == "train":
        p11 = _compile_cost(cfg1, shape, mesh, step_kind, k_inner=1)
        p21 = _compile_cost(cfg2, shape, mesh, step_kind, k_inner=1)
        p12 = _compile_cost(cfg1, shape, mesh, step_kind, k_inner=2)
        full = {}
        for k in p11:
            b = (p21[k] - p11[k]) / (L2 - L1)    # per-layer (at K=1)
            inner1 = p12[k] - p11[k]             # one extra K = a + b*L1
            a = inner1 - b * L1
            m = p11[k] - (a + b * L1)            # K-independent overhead
            full[k] = K_INNER * (a + b * L_full) + m
        return full, {"probes": [(L1, 1), (L2, 1), (L1, 2)]}
    c1 = _compile_cost(cfg1, shape, mesh, step_kind)
    c2 = _compile_cost(cfg2, shape, mesh, step_kind)
    full = {}
    for k in c1:
        b = (c2[k] - c1[k]) / (L2 - L1)
        full[k] = c1[k] + b * (L_full - L1)
    return full, {"probes": [L1, L2]}


def refine_memory(cfg, shape, mesh, step_kind, full_cost):
    """Flash-adjusted memory term for train/prefill cells.

    The probe path materializes S^2 score buffers that the production
    blockwise-flash path keeps in VMEM. Extract the S^2 bytes component
    empirically (probes at S, S/2, S/4; exact quadratic fit) and replace
    it with the flash HBM floor: K/V re-read once per Q block,
    c_flash = B_local * 2(K,V) * width * 2B / q_block per layer, with a
    3x factor on train for the flash backward re-reads.
    """
    import dataclasses
    p = _probe_period(cfg)
    cfgp = dataclasses.replace(cfg, num_layers=p)
    ss = [shape.seq_len // 4, shape.seq_len // 2, shape.seq_len]
    ts = []
    for s in ss:
        shp = dataclasses.replace(shape, seq_len=s)
        c = _compile_cost(cfgp, shp, mesh, step_kind,
                          k_inner=1 if shape.kind == "train" else None)
        ts.append(c["bytes"])
    x1, x2, x3 = ss
    t1, t2, t3 = ts
    slope12 = (t2 - t1) / (x2 - x1)
    slope13 = (t3 - t1) / (x3 - x1)
    c_quad = (slope13 - slope12) / (x3 - x2)

    # analytic flash S^2 coefficient (per probe scope: p layers, K=1)
    from repro.runtime.flags import feature
    model_size = mesh.shape.get("model", 1)
    data_size = (mesh.shape.get("data", 1)
                 * mesh.shape.get("pod", 1))
    if shape.kind == "train":
        from repro.launch.specs import K_INNER
        b_local = max(shape.global_batch // K_INNER // data_size, 1)
    else:
        b_local = max(shape.global_batch // data_size, 1)
    if feature("gqa_flat") and cfg.num_heads % model_size == 0:
        width = (cfg.num_heads // model_size) * cfg.resolved_head_dim
    else:  # grouped path: Kv replicated when Kv < model axis
        kv_local = (cfg.num_kv_heads // model_size
                    if cfg.num_kv_heads % model_size == 0
                    else cfg.num_kv_heads)
        width = kv_local * cfg.resolved_head_dim
    n_attn = sum(1 for k, _ in layer_specs_probe(cfgp) if k != "mamba")
    bwd = 3.0 if shape.kind == "train" else 1.0
    q_block = 512
    c_flash = n_attn * b_local * 2 * width * 2 * bwd / q_block

    scale = (cfg.num_layers / p) * (K_INNER if shape.kind == "train" else 1)
    s2 = shape.seq_len ** 2
    adjusted = full_cost["bytes"] - max(c_quad - c_flash, 0.0) * s2 * scale
    return {
        "bytes_flash_adjusted": adjusted,
        "c_quad_probe": c_quad,
        "c_flash_analytic": c_flash,
        "probe_seqs": ss,
    }


def layer_specs_probe(cfg):
    from repro.models.transformer import layer_specs
    return layer_specs(cfg)


def dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
           step_kind: str = "meta", donate: bool = True,
           refine: bool = False):
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    reason = skip_reason(cfg, shape)
    result = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
              "step": step_kind if shape.kind == "train" else shape.kind,
              "mesh": "2x16x16" if multi_pod else "16x16"}
    if reason:
        result["status"] = "SKIP"
        result["reason"] = reason
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    with mesh_context(mesh):
        step, args = build_step_and_args(cfg, shape, mesh, step_kind)
        if not donate:
            donate_argnums = ()
        elif shape.kind == "train":
            donate_argnums = (0,)  # phi donated to new phi
        elif shape.kind == "decode":
            donate_argnums = (1,)  # cache donated to new cache
        else:
            donate_argnums = ()
        jitted = jax.jit(step, donate_argnums=donate_argnums)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        result["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # pragma: no cover
        result["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        result["cost"] = {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float)) and
                          k in ("flops", "bytes accessed",
                                "bytes accessed output", "optimal_seconds")}
    except Exception as e:  # pragma: no cover
        result["cost"] = {"error": str(e)}

    hlo = compiled.as_text()
    coll_bytes, coll_counts = parse_collective_bytes(hlo)
    result["collective_bytes_scanbody"] = coll_bytes
    result["collective_counts_scanbody"] = coll_counts
    result["hlo_lines"] = hlo.count("\n")

    # --- exact per-step costs via unrolled probe extrapolation ---
    # (cost_analysis counts while-loop bodies once; probes unroll)
    try:
        full_cost, probe_meta = probe_costs(cfg, shape, mesh, step_kind)
        result["probe"] = probe_meta
        result["probe_cost"] = full_cost
        flops = full_cost["flops"]
        bytes_acc = full_cost["bytes"]
        coll_bytes = {k: full_cost[f"coll_bytes/{k}"] for k in COLLECTIVES}
        result["collective_bytes"] = coll_bytes
        result["collective_counts"] = {
            k: full_cost[f"coll_count/{k}"] for k in COLLECTIVES}
    except Exception as e:
        result["probe_error"] = f"{type(e).__name__}: {e}"
        flops = result.get("cost", {}).get("flops", 0.0)
        bytes_acc = result.get("cost", {}).get("bytes accessed", 0.0)
        result["collective_bytes"] = coll_bytes
    coll_total = float(sum(coll_bytes.values()))
    links = 4  # 2D/3D torus: ~4 usable ICI links per chip (v5e)
    result["roofline"] = {
        "chips": chips,
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll_total / (links * ICI_BW),
    }
    result["roofline"]["dominant"] = max(
        (("compute_s", result["roofline"]["compute_s"]),
         ("memory_s", result["roofline"]["memory_s"]),
         ("collective_s", result["roofline"]["collective_s"])),
        key=lambda kv: kv[1])[0]

    # useful-FLOPs ratio: MODEL_FLOPS = 6*N_active*D for train (fwd+bwd),
    # 2*N_active*D for inference, per chip.
    n_active = cfg.active_param_count()
    tokens = shape.seq_len * shape.global_batch
    if shape.kind == "decode":
        tokens = shape.global_batch  # one token per sequence
    mult = 6 if shape.kind == "train" else 2
    # the K inner microbatches together consume the global batch once
    model_flops_global = mult * n_active * tokens
    result["model_flops_per_chip"] = model_flops_global / chips
    if flops:
        result["useful_ratio"] = result["model_flops_per_chip"] / flops

    if refine and shape.kind in ("train", "prefill"):
        try:
            ref = refine_memory(cfg, shape, mesh, step_kind,
                                {"bytes": bytes_acc})
            result["refine"] = {k: v for k, v in ref.items()}
            result["roofline"]["memory_s_flash"] = (
                ref["bytes_flash_adjusted"] / HBM_BW)
        except Exception as e:
            result["refine_error"] = f"{type(e).__name__}: {e}"
    result["timing"] = {"lower_s": round(t_lower, 1),
                        "compile_s": round(t_compile, 1)}
    result["status"] = "OK"
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ALL_ARCHS))
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--step", default="meta", choices=["meta", "joint"])
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--opt", default="",
                    help="comma list of perf levers: "
                         "gqa_flat,banded,moe2d,ringkv")
    ap.add_argument("--refine", action="store_true",
                    help="flash-adjusted memory term (extra seq probes)")
    args = ap.parse_args()
    if args.opt:
        from repro.runtime.flags import set_features_from_env_string
        set_features_from_env_string(args.opt)
    res = dryrun(args.arch, args.shape, multi_pod=args.multi_pod,
                 step_kind=args.step, refine=args.refine)
    if args.opt:
        res["opt"] = args.opt
    text = json.dumps(res, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if res["status"] not in ("OK", "SKIP"):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
