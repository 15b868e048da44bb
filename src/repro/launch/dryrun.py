import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first backend init, and the dry-run needs 512 host placeholders.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we record:
  * memory_analysis()  — per-device argument/output/temp bytes (fits-check)
  * cost_analysis()    — HLO FLOPs and bytes accessed (roofline numerator)
  * collective bytes   — parsed from the partitioned HLO text per op kind
  * roofline terms     — compute / memory / collective seconds (TPU v5e)

Results go to artifacts/dryrun/<arch>__<shape>__<mesh>.json and feed
EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--out artifacts/dryrun]
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.launch import hlo_analysis
from repro.distributed import meshcompat
from repro.distributed import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES, cell_applicable, input_specs
from repro.models import model as M
from repro.training.optimizer import AdamWConfig, AdafactorConfig, opt_init
from repro.training.train_step import make_train_step

# --- TPU v5e hardware constants (per chip) ---
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # B/s
LINK_BW = 50e9  # B/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device output bytes of each collective op kind.

    The partitioned module's shapes are per-device; the output shape of a
    collective is what lands in each chip's memory — we use it as the
    transferred-bytes proxy (documented in EXPERIMENTS.md).
    """
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?\S+\s*=\s*(.*?)\s*(\w[\w-]*)\(", ls)
        if not m:
            continue
        op = m.group(2)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):  # e.g. all-reduce-start
                kind = c
                break
        if kind is None:
            continue
        if op.endswith("-done"):
            continue  # avoid double counting async pairs
        shapes = _SHAPE_RE.findall(m.group(1))
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    return out


_CONVERT_RE = re.compile(r"(%\S+)\s*=\s*f32\[([\d,]+)\]\S*\s+convert\(")


def cpu_upcast_artifact_bytes(hlo_text: str, min_bytes: int = 1 << 26) -> int:
    """Estimate of XLA:CPU float-normalization inflation.

    The CPU backend legalizes bf16 arithmetic to f32, materializing f32
    copies of large bf16 stacks (weights carried through lax.scan, KV
    caches).  These copies do NOT exist on TPU (native bf16).  We sum f32
    convert outputs > 64 MiB as the artifact estimate; EXPERIMENTS.md
    reports both raw and adjusted per-device memory.
    """
    total = 0
    seen = set()
    for m in _CONVERT_RE.finditer(hlo_text):
        name = m.group(1)
        if name in seen:
            continue  # computation bodies reprint op definitions
        seen.add(name)
        b = _shape_bytes("f32", m.group(2))
        if b >= min_bytes:
            total += b
    return total


def _mem_dict(mem) -> dict:
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    return {k: int(getattr(mem, k, 0)) for k in keys}


def analytic_memory_bytes(cfg, cell, mem: dict, chips: int) -> float:
    """Per-device HBM traffic model (see EXPERIMENTS.md §Roofline method).

    The CPU dry-run backend legalizes bf16 to f32 with whole-buffer convert
    fusions inside loop bodies, so HLO-derived byte counts are inflated by
    backend artifacts that do not exist on TPU.  Instead we model traffic
    from the *measured* per-device buffer assignment:

      A (args)   read once       — params, optimizer state, KV cache
      O (out)    written once
      T (temp)   written + read  — activation transients (TPU-adjusted)
      attention  re-reads the per-layer KV working set once per q-chunk
                 (blockwise attention), x3 for fwd+bwd+remat in training
    """
    A = mem["argument_size_in_bytes"]
    O = mem["output_size_in_bytes"]
    T = mem.get("temp_tpu_adjusted_bytes", mem["temp_size_in_bytes"])
    base = float(A + O + 2.0 * T)
    B, S = cell.global_batch, cell.seq_len
    if cell.kind in ("train", "prefill") and not cfg.rwkv:
        dp = 16 if B % 16 == 0 else 1
        n_micro = (16 if cfg.n_params() > 5e10 else 4) if cell.kind == "train" else 1
        b_loc = max(1, B // (dp * n_micro))
        nq = max(1, S // 1024)
        kv_layer = 2 * b_loc * S * cfg.n_kv_heads * cfg.head_dim * 2
        passes = 3 if cell.kind == "train" else 1
        base += float(cfg.n_layers * nq * kv_layer) * passes * n_micro
    return base


def model_flops_estimate(cfg, cell) -> float:
    """6*N*D (train) / 2*N*D (prefill) / 2*N_active*B (decode) + attention."""
    n_act = cfg.n_params_active()
    B, S = cell.global_batch, cell.seq_len
    L, H, hd = cfg.n_layers, cfg.n_heads, cfg.head_dim
    if cell.kind == "train":
        attn = 0.5 * 12 * L * B * S * S * H * hd  # causal fwd+bwd qk+pv
        return 6.0 * n_act * B * S + attn
    if cell.kind == "prefill":
        attn = 0.5 * 4 * L * B * S * S * H * hd
        return 2.0 * n_act * B * S + attn
    attn = 4.0 * L * B * S * H * hd  # decode reads S-deep cache
    return 2.0 * n_act * B + (attn if not cfg.sub_quadratic else 0.0)


def opt_state_shardings(mesh, opt_abs, n_experts):
    """Moments/factors inherit the param sharding rules (same tree paths)."""
    out = {}
    for key, sub in opt_abs.items():
        if key == "step":
            out[key] = SH.replicated(mesh, sub)
        else:
            out[key] = SH.param_shardings(mesh, sub, n_experts)
    return out


def build_cell(cfg, shape_name: str, mesh, baseline: bool = False):
    """Returns (fn, args, in_shardings, donate) for jit lowering.

    baseline=True reproduces the pre-§Perf substrate: XLA-auto decode
    attention (no shard_map flash-decode) and FSDP weight layout at decode.
    """
    if baseline:
        cfg = dataclasses.replace(cfg, sharded_decode_attn=False)
    cell = SHAPES[shape_name]
    specs = input_specs(cfg, shape_name)
    params_abs = M.abstract_params(cfg, jnp.bfloat16)
    p_sh = SH.param_shardings(mesh, params_abs, cfg.n_experts)

    if cell.kind == "train":
        n = cfg.n_params()
        if n > 2e11:  # 314B-class: factored second moments or it cannot fit
            opt_cfg = AdafactorConfig()
        else:
            opt_cfg = AdamWConfig(
                moment_dtype=jnp.bfloat16 if n > 5e10 else jnp.float32
            )
        # FSDP weight gathers scale with n_micro x (fwd+bwd+remat): use the
        # smallest microbatch count whose activations fit (TPU-adjusted) —
        # measured in EXPERIMENTS.md §Perf D
        n_micro = 8 if n > 5e10 else 4
        accum_dtype = jnp.bfloat16 if n > 2e11 else jnp.float32
        opt_abs = jax.eval_shape(lambda p: opt_init(p, opt_cfg), params_abs)
        o_sh = opt_state_shardings(mesh, opt_abs, cfg.n_experts)
        b_sh = SH.batch_shardings(mesh, specs["batch"])
        step_fn = make_train_step(
            cfg, opt_cfg, remat=True, n_micro=n_micro, accum_dtype=accum_dtype
        )
        return (
            step_fn,
            (params_abs, opt_abs, specs["batch"]),
            (p_sh, o_sh, b_sh),
            (0, 1),
        )
    if cell.kind == "prefill":
        b_sh = SH.batch_shardings(mesh, specs["batch"])

        def prefill_fn(params, batch):
            return M.prefill(cfg, params, batch, max_len=cell.seq_len)

        return prefill_fn, (params_abs, specs["batch"]), (p_sh, b_sh), ()
    # decode: replicate weights over 'data' (independent serving replicas)
    # when the TP-sharded copy fits v5e HBM alongside the KV cache
    if not baseline and cfg.n_params() * 2 / mesh.shape["model"] <= 6e9:
        p_sh = SH.serving_param_shardings(mesh, params_abs, cfg.n_experts)
    c_sh = SH.cache_shardings(mesh, specs["cache"])
    t_sh = SH.batch_shardings(mesh, {"tokens": specs["tokens"]})["tokens"]

    def decode_fn(params, cache, tokens):
        return M.decode_step(cfg, params, cache, tokens)

    return decode_fn, (params_abs, specs["cache"], specs["tokens"]), (p_sh, c_sh, t_sh), (1,)


def run_cell(
    arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
    baseline: bool = False,
) -> dict:
    cfg = ARCHS[arch]
    cell = SHAPES[shape_name]
    mesh_name = "multi_pod" if multi_pod else "single_pod"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "seq_len": cell.seq_len, "batch": cell.global_batch,
    }
    runs, why = cell_applicable(cfg, shape_name)
    if not runs:
        rec["status"] = "skipped"
        rec["reason"] = why
        out_dir.mkdir(parents=True, exist_ok=True)
        path = out_dir / f"{arch.replace('/', '_')}__{shape_name}__{mesh_name}.json"
        path.write_text(json.dumps(rec, indent=2, default=str))
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    try:
        with meshcompat.set_mesh(mesh):  # enables model-side sharding hints
            fn, args, in_sh, donate = build_cell(
                cfg, shape_name, mesh, baseline=baseline
            )
            t0 = time.perf_counter()
            lowered = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate).lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        hlo_text = compiled.as_text()
        mem = _mem_dict(compiled.memory_analysis())
        artifact = cpu_upcast_artifact_bytes(hlo_text)
        mem["cpu_upcast_artifact_bytes"] = artifact
        # bf16->f32 legalization at most doubles live bytes: clamp at temp/2
        mem["temp_tpu_adjusted_bytes"] = max(
            mem["temp_size_in_bytes"] // 2,
            mem["temp_size_in_bytes"] - artifact,
        )
        cost = dict(compiled.cost_analysis() or {})
        cost = {k: float(v) for k, v in cost.items() if isinstance(v, (int, float))}
        colls = parse_collectives(hlo_text)
        # XLA's cost_analysis counts while bodies ONCE; the trip-count-aware
        # analyzer (hlo_analysis.py) re-derives per-device numerators.
        ana = hlo_analysis.analyze(hlo_text)
        flops = float(ana.flops)
        bytes_accessed = analytic_memory_bytes(cfg, cell, mem, chips)
        coll_bytes = float(ana.collective_bytes)
        compute_s = flops / PEAK_FLOPS
        memory_s = bytes_accessed / HBM_BW
        collective_s = coll_bytes / LINK_BW
        mf = model_flops_estimate(cfg, cell)
        rec.update(
            status="ok",
            chips=chips,
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            memory=mem,
            flops_per_device=flops,
            bytes_per_device=bytes_accessed,
            hlo_bytes_per_device_raw=float(ana.bytes),
            collectives=ana.collectives,
            collectives_uncorrected=colls,
            collective_bytes_per_device=coll_bytes,
            cost_analysis_raw={
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            },
            roofline={
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": collective_s,
                "bottleneck": max(
                    ("compute", compute_s),
                    ("memory", memory_s),
                    ("collective", collective_s),
                    key=lambda kv: kv[1],
                )[0],
            },
            model_flops_total=mf,
            model_flops_per_device=mf / chips,
            useful_flops_ratio=(mf / chips) / flops if flops else None,
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch.replace('/', '_')}__{shape_name}__{mesh_name}.json"
    path.write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--baseline", action="store_true",
                    help="pre-optimization substrate (EXPERIMENTS.md §Perf)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, out_dir, baseline=args.baseline)
                tag = rec["status"]
                if tag == "ok":
                    n_ok += 1
                    r = rec["roofline"]
                    print(
                        f"[OK]   {arch:24s} {shape:12s} {rec['mesh']:10s} "
                        f"compile={rec['compile_s']:7.1f}s "
                        f"temp={rec['memory']['temp_size_in_bytes']/2**30:6.2f}GiB "
                        f"(tpu~{rec['memory']['temp_tpu_adjusted_bytes']/2**30:6.2f}) "
                        f"args={rec['memory']['argument_size_in_bytes']/2**30:7.2f}GiB "
                        f"bottleneck={r['bottleneck']}"
                    , flush=True)
                elif tag == "skipped":
                    n_skip += 1
                    print(f"[SKIP] {arch:24s} {shape:12s} {rec['mesh']:10s} {rec['reason']}", flush=True)
                else:
                    n_err += 1
                    print(f"[ERR]  {arch:24s} {shape:12s} {rec['mesh']:10s} {rec['error']}", flush=True)
    print(f"done: ok={n_ok} skip={n_skip} err={n_err}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
