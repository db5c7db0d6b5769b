import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run (and only the dry-run) builds the production meshes on 512
# host-platform placeholder devices; smoke tests and benches see 1 device.

import argparse
import json
import math
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, RunConfig, get_config, list_archs, shape_applicable
from repro.launch import hlo_analysis, hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import TrainState, make_prefill_step, make_serve_step, make_train_step
from repro.models import model as M
from repro.parallel import sharding as S
from repro.parallel.api import axis_rules, logical_spec

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# v5e hardware constants (roofline terms derive from these; the chip-level
# pair has ONE definition, repro.obs.constants.DEVICE_PEAKS). Records also carry the RAW hlo flops/bytes so
# benchmarks/roofline.py can re-price old artifacts under changed or
# calibrated constants without re-running the dry run.
from repro.obs.constants import DEVICE_PEAKS  # noqa: E402

PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"].peak_flops
HBM_BW = DEVICE_PEAKS["TPU v5 lite"].hbm_bw

LINK_BW = 50e9  # B/s / link ICI


def run_overrides(cfg, shape) -> RunConfig:
    big = M.count_params_analytic(cfg) > 5e10
    return RunConfig(
        moment_dtype="bfloat16" if big else "float32",
        grad_accum=8 if shape.kind == "train" else 1,
        remat="full" if shape.kind == "train" else "none",
        # §Perf decode lever: int8 KV cache (quantization error property-tested)
        kv_cache_dtype="int8" if shape.kind == "decode" else "bfloat16",
    )


def _rep(mesh, tree):
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)


def build_cell(cfg, shape, run, mesh):
    """Returns (fn, abstract_args, in_shardings, out_shardings)."""
    dt = jnp.bfloat16
    pshard, pshapes = S.params_sharding(cfg, mesh, dt)
    specs = M.input_specs(cfg, shape, dt)
    bshard = S.batch_sharding(specs, mesh)

    if shape.kind == "train":
        oshard, oshapes = S.opt_sharding(cfg, mesh, run, pshapes)
        state_shapes = TrainState(params=pshapes, opt=oshapes)
        state_shard = TrainState(params=pshard, opt=oshard)
        fn = make_train_step(cfg, run, grad_shardings=pshard)
        metrics_abs = {k: jax.ShapeDtypeStruct((), jnp.float32) for k in ("loss", "grad_norm", "lr")}
        return (fn, (state_shapes, specs), (state_shard, bshard),
                (state_shard, _rep(mesh, metrics_abs)))

    cache_dt = jnp.int8 if run.kv_cache_dtype == "int8" else dt
    cshard, cshapes = S.cache_sharding(cfg, mesh, shape.global_batch, shape.seq_len, cache_dt)
    if shape.kind == "prefill":
        fn = make_prefill_step(cfg, run)
        logit_shard = NamedSharding(mesh, logical_spec(
            (shape.global_batch, shape.seq_len, cfg.vocab_size), ("batch", None, "vocab"), mesh))
        return (fn, (pshapes, cshapes, specs), (pshard, cshard, bshard),
                (logit_shard, cshard))
    # decode
    fn = make_serve_step(cfg, run)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    tok_shard = NamedSharding(mesh, logical_spec((shape.global_batch,), ("batch",), mesh))
    return (fn, (pshapes, cshapes, specs, pos),
            (pshard, cshard, bshard, NamedSharding(mesh, P())),
            (tok_shard, cshard))


def analytic_flash_bytes(cfg, shape, run, qc: int = 256, kc: int = 512) -> float:
    """Global HBM bytes of all attention, if computed by the Pallas flash
    kernel (kernels/flash_attention): per call, Q and O stream once, K/V
    re-stream once per q-block (the kernel's BlockSpec schedule); backward
    re-streams per its two passes; remat='full' runs forward twice.

    This is exactly the operand/result traffic compiled.as_text() would show
    for the pallas custom-call on a real TPU lowering — substituted here
    because the CPU dry-run lowers the (numerically identical) jnp path."""
    import math

    from repro.models.model import AUDIO_DEC_LAYOUT, AUDIO_ENC_LAYOUT
    from repro.models.transformer import group_layout, n_groups

    b = shape.global_batch
    s = shape.seq_len
    kind = shape.kind
    dt = 2  # bf16
    h = cfg.n_heads

    # int8 KV cache: K/V stream at 1 byte (+ scales) in the decode kernel
    kv_dt = 1 if (kind == "decode" and run.kv_cache_dtype == "int8") else dt

    def call_bytes(sq, sk, kv, g, dk, dv, train, kv_bytes=dt):
        nq = max(1, math.ceil(sq / qc))
        nk = max(1, math.ceil(sk / kc))
        qb = b * sq * kv * g * dk * dt
        ob = b * sq * kv * g * dv * dt
        kb = b * sk * kv * dk * kv_bytes + (b * sk * kv * 4 if kv_bytes == 1 else 0)
        vb = b * sk * kv * dv * kv_bytes + (b * sk * kv * 4 if kv_bytes == 1 else 0)
        fwd = qb + ob + nq * (kb + vb)
        if not train:
            return fwd
        bwd = (nq * (kb + vb) + 2 * qb + ob  # dq pass
               + nk * (qb + ob) + kb + vb)  # dk/dv pass
        n_fwd = 2 if run.remat == "full" else 1
        return n_fwd * fwd + bwd

    def sub_dims(sub):
        if sub.kind == "mla":
            return (1, h, cfg.kv_lora_rank + cfg.rope_head_dim, cfg.kv_lora_rank)
        kv = cfg.n_kv_heads
        return (kv, h // kv, cfg.resolved_head_dim, cfg.resolved_head_dim)

    train = kind == "train"
    sq = 1 if kind == "decode" else s
    total = 0.0
    layouts = []
    if cfg.is_encoder_decoder:
        if kind != "decode":
            layouts.append((AUDIO_ENC_LAYOUT, cfg.n_encoder_layers, s))
        layouts.append((AUDIO_DEC_LAYOUT, cfg.n_layers, s))
    else:
        layouts.append((group_layout(cfg), n_groups(cfg), s))
    for lay, groups, sk_default in layouts:
        for sub in lay:
            if sub.kind not in ("attn", "cross", "mla"):
                continue
            sk = sk_default
            if sub.kind == "cross" and cfg.family == "vlm":
                sk = cfg.n_image_tokens
            kv, g, dk, dv = sub_dims(sub)
            kvb = kv_dt if sub.kind == "attn" else dt  # only GQA caches quantize
            total += groups * call_bytes(sq, sk, kv, g, dk, dv, train, kv_bytes=kvb)
    return total


def model_flops(cfg, shape) -> float:
    n = M.count_params_analytic(cfg)
    na = M.count_params_analytic(cfg, active_only=True)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if shape.kind == "train":
        return 6.0 * na * tokens
    return 2.0 * na * tokens


def run_cell(arch: str, shape_name: str, multi_pod: bool, force: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    out_path = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    run = run_overrides(cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.devices.shape)
    try:
        with mesh, axis_rules(mesh, fsdp=run.fsdp):
            fn, args, in_sh, out_sh = build_cell(cfg, shape, run, mesh)
            t0 = time.time()
            lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            cost = compiled.cost_analysis() or {}
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
        # loop-aware analysis: XLA's cost_analysis counts while bodies once,
        # which undercounts scan-over-layers/grad-accum programs ~100x.
        la = hlo_cost.analyze(hlo, tags=("flash_attention",))
        coll = {
            "bytes_by_kind": la["collective_bytes_by_kind"],
            "counts": la["collective_counts"],
            "total_bytes": la["collective_bytes"],
            # TPU-native dtype normalization: the CPU backend promotes bf16
            # GEMM operands to f32 and hoists converts above collectives;
            # `native` counts bf16 bytes for those (what the TPU target moves)
            "total_bytes_native": la["collective_bytes_native"],
            "native_by_kind": la["collective_native_by_kind"],
        }
        flops = float(la["flops"])
        bytes_hbm = float(la["bytes"])
        mf = model_flops(cfg, shape)
        mem_fields = {}
        if mem is not None:
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
                try:
                    mem_fields[f] = int(getattr(mem, f))
                except Exception:
                    pass
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            n_chips=n_chips,
            grad_accum=run.grad_accum,
            moment_dtype=run.moment_dtype,
            hlo_flops_per_device=flops,
            hlo_bytes_per_device=bytes_hbm,
            xla_cost_analysis_raw={"flops": float(cost.get("flops", 0.0)),
                                   "bytes": float(cost.get("bytes accessed", 0.0))},
            collectives=coll,
            memory_analysis=mem_fields,
            model_flops_global=mf,
            # roofline terms (seconds), per the spec's formulas; the collective
            # term uses dtype-normalized bytes (see coll.total_bytes_native)
            compute_term_s=flops / PEAK_FLOPS,
            memory_term_s=bytes_hbm / HBM_BW,
            collective_term_s=coll["total_bytes_native"] / (3 * LINK_BW),
            collective_term_raw_s=coll["total_bytes"] / (3 * LINK_BW),
        )
        terms = {
            "compute": rec["compute_term_s"],
            "memory": rec["memory_term_s"],
            "collective": rec["collective_term_s"],
        }
        rec["dominant_term"] = max(terms, key=terms.get)
        rec["useful_flop_ratio"] = (mf / n_chips) / flops if flops else 0.0
        # beyond-paper §Perf variant: attention via the Pallas flash kernel
        # (validated in kernels/flash_attention) — substitute the tagged jnp
        # attention bytes with the kernel's streaming traffic.
        tagged = float(la["tagged_bytes"].get("flash_attention", 0.0))
        if tagged > 0:
            kern_bytes = analytic_flash_bytes(cfg, shape, run) / n_chips
            bytes_pallas = max(bytes_hbm - tagged + kern_bytes, 0.0)
            rec["pallas_flash"] = {
                "attention_bytes_jnp": tagged,
                "attention_bytes_kernel": kern_bytes,
                "memory_term_pallas_s": bytes_pallas / HBM_BW,
            }
        print(f"[dryrun] {arch} {shape_name} {mesh_name}: lower {rec['lower_s']}s "
              f"compile {rec['compile_s']}s dominant={rec['dominant_term']}")
        if mem is not None:
            print(f"  memory_analysis: {mem_fields}")
        print(f"  cost_analysis: flops={flops:.3e} bytes={bytes_hbm:.3e} "
              f"collective_bytes={coll['total_bytes']:.3e}")
    except Exception as e:  # a failing cell is a bug: record and re-raise visibility
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] {arch} {shape_name} {mesh_name}: FAILED {type(e).__name__}: {e}")
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    archs = [a for a in list_archs() if a != "vgg19-sparse"] if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    n_bad = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, force=args.force)
                n_bad += rec.get("status") == "error"
    if n_bad:
        raise SystemExit(f"{n_bad} cells failed")


if __name__ == "__main__":
    main()
