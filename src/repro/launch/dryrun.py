import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (spec deliverable e).

Lowers + compiles every (architecture × input shape) cell for the single-pod
(16×16) and multi-pod (2×16×16) production meshes on 512 placeholder host
devices, records ``memory_analysis()`` / ``cost_analysis()`` / HLO-parsed
collective bytes, and writes one JSON per cell.

Usage:
  python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k \
      --mesh single --out experiments/dryrun
  python -m repro.launch.dryrun --all [--mesh both] [--out DIR]

``--all`` runs each cell in a fresh subprocess (isolation: one failing cell
cannot kill the sweep) and skips cells whose JSON already exists.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import api
from repro.models.common import ArchConfig
from repro.launch import mesh as M
from repro.launch import sharding as shd
from repro.optim.adamw import AdamW, AdamWState

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
from benchmarks import calculator, hlo_analysis  # noqa: E402

SHAPES = list(api.SHAPES)
MESHES = {"single": False, "multi": True}


def should_skip(cfg: ArchConfig, shape_name: str) -> str:
    """Spec-mandated skips, recorded (not silently dropped)."""
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return ("skipped: long_500k needs sub-quadratic attention; "
                f"{cfg.name} is pure full-attention (DESIGN.md §4)")
    return ""


def _depth_multipliers(cfg: ArchConfig, shape: api.ShapeSpec):
    """Execution-count multiplier per while-nesting depth (hlo_analysis)."""
    s = shape.seq
    nq = max(s // 2048, 1)
    nk = max(s // 1024, 1)
    if shape.kind == "train":
        inner = cfg.attn_every if cfg.family == "hybrid" else cfg.n_layers
        return [1, cfg.microbatches, cfg.microbatches * inner,
                cfg.microbatches * inner * nq,
                cfg.microbatches * inner * nq * nk]
    if shape.kind == "prefill":
        if cfg.family == "ssm":       # xlstm prefill scans over tokens
            return [1, s, s * cfg.n_layers]
        inner = cfg.attn_every if cfg.family == "hybrid" else cfg.n_layers
        return [1, inner, inner * nq, inner * nq * nk]
    # decode
    inner = cfg.attn_every if cfg.family == "hybrid" else cfg.n_layers
    return [1, inner]


def run_cell(arch_id: str, shape_name: str, mesh_name: str) -> dict:
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "ok": False, "opts": os.environ.get("REPRO_OPTS", "")}
    t0 = time.time()

    if arch_id == "totem-rmat":
        return run_graph_cell(shape_name, mesh_name, rec)

    cfg = configs.get(arch_id)
    if ("serve_bf16" in os.environ.get("REPRO_OPTS", "")
            and api.SHAPES.get(shape_name)
            and api.SHAPES[shape_name].kind != "train"):
        # §Perf: serving stores bf16 weights (standard practice) — halves
        # both the resident parameter bytes and the f32→bf16 convert temps.
        import dataclasses as _dc
        cfg = _dc.replace(cfg, param_dtype="bfloat16")
    skip = should_skip(cfg, shape_name)
    if skip:
        rec.update(ok=True, skipped=skip)
        return rec

    shape = api.SHAPES[shape_name]
    mesh = M.make_production_mesh(multi_pod=MESHES[mesh_name])
    model = api.build(cfg)
    params_shape = model.params_shape()
    pspecs = shd.param_specs(params_shape, mesh)
    batch_shape = api.input_specs(cfg, shape)
    num_chips = 512 if MESHES[mesh_name] else 256
    # the single-pod mesh uses only half the placeholder devices
    rec["chips"] = num_chips

    if shape.kind == "train":
        opt = AdamW()
        opt_shape = jax.eval_shape(opt.init, params_shape)
        ospecs = AdamWState(step=shd.P(),
                            mu=shd.param_specs(opt_shape.mu, mesh),
                            nu=shd.param_specs(opt_shape.nu, mesh))
        bspecs = shd.batch_specs(batch_shape, mesh)
        step = api.make_train_step(model, opt)

        def wrapped(params, opt_state, batch):
            with shd.activation_rules(mesh, seq_sharded="seq_shard" in os.environ.get("REPRO_OPTS", "")):
                return step(params, opt_state, batch)

        donate = ((0, 1) if "donate" in
                  os.environ.get("REPRO_OPTS", "") else ())
        jitted = jax.jit(
            wrapped,
            in_shardings=(shd.named(pspecs, mesh),
                          shd.named(ospecs, mesh),
                          shd.named(bspecs, mesh)),
            out_shardings=(shd.named(pspecs, mesh),
                           shd.named(ospecs, mesh), None),
            donate_argnums=donate)
        args = (params_shape, opt_shape, batch_shape)
    elif shape.kind == "prefill":
        bspecs = shd.batch_specs(batch_shape, mesh)

        def wrapped(params, batch):
            with shd.activation_rules(mesh, seq_sharded="seq_shard" in os.environ.get("REPRO_OPTS", "")):
                return model.prefill(params, batch)

        jitted = jax.jit(wrapped,
                         in_shardings=(shd.named(pspecs, mesh),
                                       shd.named(bspecs, mesh)))
        args = (params_shape, batch_shape)
    else:  # decode
        cache_shape = batch_shape["cache"]
        cspecs = _cache_specs(cache_shape, mesh)
        tok_spec = shd.batch_specs({"tokens": batch_shape["tokens"]},
                                   mesh)["tokens"]

        def wrapped(params, cache, tokens):
            with shd.activation_rules(mesh, seq_sharded="seq_shard" in os.environ.get("REPRO_OPTS", "")):
                return model.decode_step(params, cache, tokens)

        jitted = jax.jit(
            wrapped,
            in_shardings=(shd.named(pspecs, mesh),
                          shd.named(cspecs, mesh),
                          jax.sharding.NamedSharding(mesh, tok_spec)),
            donate_argnums=(1,))
        args = (params_shape, cache_shape, batch_shape["tokens"])

    lowered = jitted.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    rec["memory_analysis"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_estimate_gib": round(
            (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30, 3),
    }
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis_raw"] = {
        "flops": ca.get("flops", 0.0),
        "bytes_accessed": ca.get("bytes accessed", 0.0),
    }
    mults = _depth_multipliers(configs.get(arch_id), api.SHAPES[shape_name])
    cb = hlo_analysis.collective_bytes(compiled.as_text(), mults)
    rec["collective_bytes"] = {k: v for k, v in cb.items()
                               if k != "by_depth"}
    rec["collective_by_depth"] = cb["by_depth"]
    rec["depth_multipliers"] = mults

    roof = calculator.analyze(configs.get(arch_id), api.SHAPES[shape_name],
                              num_chips,
                              ici_bytes_measured=cb["total"] / num_chips
                              if cb["total"] else None)
    rec["roofline"] = roof.as_dict()
    rec["ok"] = True
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def _cache_specs(cache_shape, mesh):
    """Decode caches: batch dim sharded over data(+pod), head/expert dims on
    model where divisible."""
    fsdp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))

    seq_shard = "cache_seq_shard" in os.environ.get("REPRO_OPTS", "")

    def leaf(x):
        if len(x.shape) == 0:
            return shd.P()
        spec = [None] * len(x.shape)
        # leading L/group axis unsharded; batch axis = index 1 where present
        bdim = 1 if len(x.shape) >= 2 else 0
        if x.shape[bdim] % (int(jnp.prod(jnp.asarray(
                [mesh.shape[a] for a in fsdp])))) == 0:
            spec[bdim] = fsdp
        # cache_seq_shard (§Perf): split-KV decode — shard the sequence dim
        # of [L, B, S, G, hd] caches over 'model' (the attention contraction
        # partitions cleanly; kv-head counts rarely divide the axis).
        if (seq_shard and len(x.shape) >= 5
                and x.shape[2] % mesh.shape["model"] == 0):
            spec[2] = "model"
        # default: kv-head axis over model when divisible (axis -2 for k/v)
        elif len(x.shape) >= 4 and x.shape[-2] % mesh.shape["model"] == 0:
            spec[-2] = "model"
        elif len(x.shape) >= 3 and x.shape[-1] % mesh.shape["model"] == 0:
            spec[-1] = "model"
        return shd.P(*spec)

    return jax.tree.map(leaf, cache_shape)


# ---------------------------------------------------------------------------
# The paper's own workload: BSP PageRank superstep on 512 partitions
# ---------------------------------------------------------------------------

def run_graph_cell(shape_name: str, mesh_name: str, rec: dict) -> dict:
    """Lower the TOTEM BSP superstep for RMAT28-like partition shapes."""
    import numpy as np
    from repro.core.bsp import _superstep, _Dims, VertexProgram, SUM

    t0 = time.time()
    multi = MESHES[mesh_name]
    n_dev = 512 if multi else 256
    mesh = jax.make_mesh((n_dev,), ("parts",))
    scale, ef = 28, 16
    v_total, e_total = 1 << scale, (1 << scale) * ef
    v_max = -(-v_total // n_dev)
    e_max = int(e_total / n_dev * 1.5)          # skew headroom
    o_max = min(v_max, e_max) // 4
    dims = _Dims(n_dev, v_max, e_max, o_max)

    msg_dtype = (jnp.bfloat16 if "graph_bf16_msgs" in
                 os.environ.get("REPRO_OPTS", "") else jnp.float32)

    def edge_fn(state, src, weight, step):
        from repro.core.bsp import gather_src
        return gather_src(state["rank"], src).astype(msg_dtype)

    def apply_fn(state, acc, step):
        acc = acc.astype(jnp.float32)
        return {"rank": 0.15 / v_total + 0.85 * acc}, jnp.bool_(True)

    program = VertexProgram(combine=SUM, edge_fn=edge_fn, apply_fn=apply_fn,
                            max_steps=20)

    def local_fn(state, edges):
        def exchange(outbox):
            pl = outbox.shape[0]
            ob = outbox.reshape(pl, n_dev, pl, outbox.shape[-1])
            recv = jax.lax.all_to_all(ob, "parts", split_axis=1,
                                      concat_axis=0, tiled=False)
            recv = recv.transpose(2, 0, 1, 3)
            return recv.reshape(pl, n_dev * pl, outbox.shape[-1])

        def fin(x):
            return jax.lax.psum(jnp.int32(0), "parts") == 0

        state, _ = _superstep(dims, program, edges, exchange, fin, None,
                              state, jnp.int32(0))
        return state

    P = jax.sharding.PartitionSpec
    sds = jax.ShapeDtypeStruct
    state = {"rank": sds((n_dev, v_max), jnp.float32)}
    edges = {"src": sds((n_dev, e_max), jnp.int32),
             "dst_ext": sds((n_dev, e_max), jnp.int32),
             "inbox_dst": sds((n_dev, n_dev, o_max), jnp.int32)}
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(jax.tree.map(lambda _: P("parts"), state),
                                 jax.tree.map(lambda _: P("parts"), edges)),
                       out_specs=jax.tree.map(lambda _: P("parts"), state),
                       check_vma=False)
    jitted = jax.jit(fn)
    lowered = jitted.lower(state, edges)
    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)
    ma = compiled.memory_analysis()
    rec["memory_analysis"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "peak_estimate_gib": round(
            (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30, 3),
    }
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis_raw"] = {"flops": ca.get("flops", 0.0),
                                "bytes_accessed": ca.get("bytes accessed",
                                                         0.0)}
    cb = hlo_analysis.collective_bytes(compiled.as_text(), [1])
    rec["collective_bytes"] = {k: v for k, v in cb.items()
                               if k != "by_depth"}
    # analytic: one superstep ≈ memory-bound edge traffic
    hbm = e_max * 8 + v_max * 4 * 3 + n_dev * o_max * 4 * 2
    rec["roofline"] = {
        "flops": 2.0 * e_max, "hbm_bytes": float(hbm),
        "ici_bytes": cb["total"] / n_dev,
        "model_flops": 2.0 * e_total,
        "compute_s": 2.0 * e_max / calculator.PEAK_FLOPS,
        "memory_s": hbm / calculator.HBM_BW,
        "collective_s": (cb["total"] / n_dev) / calculator.ICI_BW,
        "dominant": "memory",
        "useful_ratio": 1.0,
    }
    rec["ok"] = True
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def all_cells():
    cells = [(a, s) for a in configs.all_ids() for s in SHAPES]
    cells.append(("totem-rmat", "pagerank_superstep"))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    if args.all:
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        failures = 0
        for arch, shape in all_cells():
            for mesh_name in meshes:
                path = out / f"{arch}__{shape}__{mesh_name}.json"
                if path.exists() and json.loads(path.read_text()).get("ok"):
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", mesh_name, "--out", str(out)]
                print(f"[dryrun] {arch} × {shape} × {mesh_name} ...",
                      flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    failures += 1
                    path.write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mesh_name,
                         "ok": False, "error": r.stderr[-4000:]}, indent=1))
                    print(f"  FAILED: {r.stderr.splitlines()[-1][:200]}"
                          if r.stderr else "  FAILED", flush=True)
                else:
                    print("  ok", flush=True)
        return 1 if failures else 0

    from repro.runtime.failures import RETRYABLE_EXCEPTIONS

    rec = {}
    try:
        rec = run_cell(args.arch, args.shape, args.mesh)
    except (ValueError, TypeError, NotImplementedError,
            RuntimeError) + RETRYABLE_EXCEPTIONS:
        # Expected dry-run outcomes (shape/config rejections, XLA compile
        # and runtime errors, worker faults) become a failed cell record;
        # anything else — a programming bug — propagates with a traceback.
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "ok": False, "error": traceback.format_exc()[-4000:]}
    path = out / f"{args.arch}__{args.shape}__{args.mesh}.json"
    path.write_text(json.dumps(rec, indent=1, default=float))
    if rec.get("ok"):
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "ok") if k in rec}))
        if "memory_analysis" in rec:
            print("memory:", rec["memory_analysis"])
            print("cost:", rec["cost_analysis_raw"])
        return 0
    print(rec.get("error", "")[-2000:], file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
