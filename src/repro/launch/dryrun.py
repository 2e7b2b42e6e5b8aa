import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, record memory / cost / collective terms.

The two lines above MUST stay first: jax locks the device count on first
initialization, and the 512 placeholder host devices exist only inside this
entry point (tests and benches see 1 device).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k --multi-pod both --out results/dryrun.json
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHITECTURES, SHAPES, get_config, shape_for
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.inputs import (
    train_batch_specs, decode_specs, to_named)
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import (
    RunConfig, abstract_params, param_pspecs, lm_loss, decode_step, prefill,
)
from repro.optim.adamw import AdamWConfig
from repro.sharding.specs import ShardingRules
from repro.train.state import abstract_train_state, train_state_pspecs
from repro.train.step import make_train_step

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _type_bytes(type_str: str) -> int:
    """Total bytes of all array shapes inside an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective byte totals parsed from post-SPMD HLO.

    Volume per op = max(result bytes, operand bytes) — covers both
    all-gather (result larger) and reduce-scatter (operand larger).
    ``*-start`` ops are counted; their ``*-done`` twins are skipped.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", line)
        if not m:
            continue
        result_type, opname = m.groups()
        base = opname.removesuffix("-start")
        if base not in _COLLECTIVES or opname.endswith("-done"):
            continue
        args = line[m.end() - 1:]
        vol = max(_type_bytes(result_type), _type_bytes(args))
        out[base] += vol
        out["count"] += 1
    return out


def _mem_dict(ma) -> dict:
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
    }


def choose_microbatch(global_batch: int, dp_total: int, target_mb: int) -> int:
    """Largest accumulation factor <= target that keeps every microbatch
    divisible by the data-parallel degree."""
    for m in sorted({target_mb, 16, 8, 4, 2, 1}, reverse=True):
        if m <= target_mb and global_batch % m == 0 \
                and (global_batch // m) % dp_total == 0:
            return m
    return 1


def run_config_for(cfg: ModelConfig, shape: ShapeSpec, dp_total: int,
                   overrides=None) -> RunConfig:
    """Per-cell execution knobs (microbatching keyed to model size)."""
    big = cfg.d_model >= 5000 or cfg.param_counts()[0] > 2e10
    target = 16 if big else (8 if cfg.d_model >= 2048 else 4)
    mb = choose_microbatch(shape.global_batch, dp_total, target) \
        if shape.mode == "train" else 0
    kw = dict(microbatch=mb, remat=True)
    if overrides:
        kw.update(overrides)
    return RunConfig(**kw)


def dryrun_cell(arch: str, shape_name: str, *, multi_pod,
                rc_overrides=None,
                rules_overrides=None,
                opt_cfg=None,
                serve_params_dtype=None,
                train_lowmem: bool = False,
                variant: str = "baseline") -> dict:
    """Lower + compile one cell; returns the roofline record."""
    cfg = get_config(arch)
    shape = shape_for(cfg, shape_name)
    if shape is None:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skip(full-attn)",
                "note": "long_500k skipped: pure full-attention arch "
                        "(DESIGN.md §Arch-applicability)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    dp_total = mesh.shape["data"] * mesh.shape.get("pod", 1)
    # batch-1 long decode: shard the KV cache sequence instead of batch
    seq_sharded = (shape.mode == "decode"
                   and shape.global_batch % dp_total != 0)
    rules = ShardingRules.for_mesh(mesh, seq_sharded=seq_sharded)
    if rules_overrides:
        rules = rules.with_overrides(**rules_overrides)
    rc = run_config_for(cfg, shape, dp_total, rc_overrides)

    t0 = time.time()
    if shape.mode == "train":
        if train_lowmem:       # bf16 adam moments + bf16 master weights
            state_sds = abstract_train_state(
                cfg, opt_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        else:
            state_sds = abstract_train_state(cfg)
        state_ps = train_state_pspecs(cfg, rules)
        batch_sds, batch_ps = train_batch_specs(cfg, shape, rules)
        step = make_train_step(cfg, rules, rc, opt_cfg or AdamWConfig())
        jitted = jax.jit(
            step,
            in_shardings=(to_named(rules, state_ps),
                          to_named(rules, batch_ps)),
            donate_argnums=(0,))
        lowered = jitted.lower(state_sds, batch_sds)
    elif shape.mode == "prefill":
        params_sds = abstract_params(cfg, serve_params_dtype)
        params_ps = param_pspecs(cfg, rules)
        batch_sds, batch_ps = train_batch_specs(cfg, shape, rules,
                                                with_labels=False)

        def prefill_step(params, batch):
            return prefill(params, cfg, rules, batch["tokens"], rc=rc,
                           prefix_embed=batch.get("prefix_embed"),
                           encoder_frames=batch.get("encoder_frames"))

        jitted = jax.jit(
            prefill_step,
            in_shardings=(to_named(rules, params_ps),
                          to_named(rules, batch_ps)))
        lowered = jitted.lower(params_sds, batch_sds)
    else:                                   # decode
        params_sds = abstract_params(cfg, serve_params_dtype)
        params_ps = param_pspecs(cfg, rules)
        (cache_sds, token_sds), (cache_ps, token_ps) = \
            decode_specs(cfg, shape, rules)

        def serve_step(params, cache, token):
            return decode_step(params, cfg, rules, cache, token, rc=rc)

        jitted = jax.jit(
            serve_step,
            in_shardings=(to_named(rules, params_ps),
                          to_named(rules, cache_ps),
                          to_named(rules, token_ps)),
            donate_argnums=(1,))
        lowered = jitted.lower(params_sds, cache_sds, token_sds)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    mem = _mem_dict(compiled.memory_analysis())
    colls = collective_bytes(compiled.as_text())
    tot, act = cfg.param_counts()
    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "variant": variant,
        "rc": {"microbatch": rc.microbatch, "causal_skip": rc.causal_skip,
               "remat_policy": rc.remat_policy},
        "serve_dtype": serve_params_dtype or "float32",
        "status": "ok", "n_chips": n_chips,
        "mode": shape.mode, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "microbatch": rc.microbatch,
        "params_total": tot, "params_active": act,
        "seq_sharded": seq_sharded,
        # cost_analysis is PER-DEVICE, post-SPMD; scans count ONE trip
        # (see EXPERIMENTS.md §Roofline methodology + analytical correction)
        "hlo_flops_per_dev": float(cost.get("flops", -1.0)),
        "hlo_bytes_accessed_per_dev": float(cost.get("bytes accessed", -1.0)),
        "memory": mem,
        "collectives": colls,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    return rec


#: the §Perf-winning serving configuration (EXPERIMENTS.md): TP-only
#: params (no FSDP at inference), sequence-sharded decode caches, bf16
#: weight streams, causal block skipping, group-local MoE dispatch.
OPTIMIZED_SERVE = dict(
    rules_overrides={"d": (), "cache_seq": ("model",), "hd": (),
                     "kvheads": (), "moe_groups": 16},
    serve_params_dtype="bfloat16",
    rc_overrides={"causal_skip": True, "q_chunk": 2048},
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--serve-optimized", action="store_true",
                    help="apply the §Perf serving configuration to "
                         "prefill/decode cells (baseline runs without)")
    args = ap.parse_args()

    archs = list(ARCHITECTURES) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True],
            "both": [False, True]}[args.multi_pod]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    records = []
    if args.append and os.path.exists(args.out):
        records = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["multi_pod"]) for r in records}

    for arch in archs:
        for shape in shapes:
            for mp in pods:
                if (arch, shape, mp) in done:
                    continue
                tag = f"{arch} x {shape} x {'2pod' if mp else '1pod'}"
                try:
                    kw = {}
                    if args.serve_optimized and \
                            SHAPES[shape].mode != "train":
                        kw = dict(OPTIMIZED_SERVE,
                                  variant="serve_optimized")
                    rec = dryrun_cell(arch, shape, multi_pod=mp, **kw)
                    if rec["status"] == "ok":
                        print(f"[ok] {tag}: flops/dev={rec['hlo_flops_per_dev']:.3e} "
                              f"coll={sum(rec['collectives'][k] for k in _COLLECTIVES)/1e6:.1f}MB "
                              f"compile={rec['compile_s']}s", flush=True)
                    else:
                        print(f"[skip] {tag}: {rec['status']}", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"[ERR] {tag}: {type(e).__name__}: {e}", flush=True)
                records.append(rec)
                json.dump(records, open(args.out, "w"), indent=1)
    ok = sum(1 for r in records if r["status"] == "ok")
    sk = sum(1 for r in records if r["status"].startswith("skip"))
    er = sum(1 for r in records if r["status"] == "error")
    print(f"dry-run complete: {ok} ok, {sk} documented skips, {er} errors")


if __name__ == "__main__":
    main()
