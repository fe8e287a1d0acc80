import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init); do not move them.

For each cell this driver:
  1. builds the full-size config (long_500k switches pure-attention archs to
     the paper's mosa_hybrid mode — MoSA global heads + sliding-window local
     heads; ssm/hybrid archs run natively);
  2. lowers the right step with ShapeDtypeStruct inputs (no allocation):
       train_4k    -> train_step (fwd + bwd + AdamW update)
       prefill_32k -> model.prefill (forward + cache write)
       decode_*    -> serve_step (one token against a seq_len KV cache)
  3. ``.compile()``s it for the production mesh (16x16 or 2x16x16),
  4. records memory_analysis / cost_analysis / parsed collective bytes into
     ``experiments/dryrun/<mesh>/<arch>__<shape>.json``.

CLI:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config, config_names
from repro.configs.shapes import SHAPES, input_specs
from repro.dist import sharding as shd
from repro.dist import hints
from repro.launch.mesh import make_production_mesh
from repro.nn.module import init_shapes
from repro.nn.transformer import TransformerLM
from repro.optim import schedules
from repro.optim.optimizer import adamw, apply_updates

ARCHS = [
    "granite-moe-1b-a400m", "deepseek-v2-lite-16b", "jamba-v0.1-52b",
    "musicgen-large", "yi-34b", "yi-9b", "gemma3-4b", "qwen2-1.5b",
    "xlstm-125m", "qwen2-vl-72b",
]

# archs whose long_500k cell runs natively (recurrent state); everything else
# switches to the paper's MoSA+local mode for that shape.
NATIVE_LONG = {"xlstm-125m", "jamba-v0.1-52b"}

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\b")
SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
OPNAME_RE = re.compile(r'op_name="([^"]*)"')
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3fn": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2}
# effective per-chip traffic multiplier on the printed (per-shard) shape
ALGO_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}


def parse_collective_bytes(hlo_text: str, trip_counts=(1,)):
    """Per-device collective traffic bytes by op kind, from partitioned HLO.

    XLA prints each while (scan) body once; an op whose op_name metadata
    contains d occurrences of "/while/" executes prod(trip_counts[:d]) times
    per step.  ``trip_counts[d-1]`` is the trip count of loop nesting level d
    (level 1 = the layer scan).  Bytes are also recorded per depth so the
    correction's impact is auditable.
    """
    out = {}
    by_depth = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if m is None or "=" not in line:
            continue
        kind = m.group(1)
        rhs = line.split("=", 1)[1]
        head = rhs.split(m.group(0))[0]
        # Result type only.  For tuple results (e.g. all-gather-start's
        # (operand, result) pair) take the LARGEST element — summing every
        # annotation double-counts the traffic.
        sizes = []
        for dt, dims in SHAPE_RE.findall(head):
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            sizes.append(n * DTYPE_BYTES[dt])
        bytes_ = max(sizes) if sizes else 0
        opn = OPNAME_RE.search(line)
        depth = opn.group(1).count("/while/") if opn else 0
        mult = 1.0
        for lvl in range(min(depth, len(trip_counts))):
            mult *= trip_counts[lvl]
        eff = bytes_ * ALGO_FACTOR[kind] * mult
        out[kind] = out.get(kind, 0) + eff
        by_depth[depth] = by_depth.get(depth, 0) + eff
        out.setdefault("_ops", 0)
        out["_ops"] += 1
    out["total"] = sum(v for k, v in out.items() if not k.startswith("_"))
    out["_by_depth"] = by_depth
    out["_trip_counts"] = list(trip_counts)
    return out


def build_cfg(arch: str, shape_name: str, mosa: bool = False,
              remat: str | None = None):
    cfg = get_config(arch, preset="full")
    shape = SHAPES[shape_name]
    note = ""
    if shape_name == "long_500k" and arch not in NATIVE_LONG:
        cfg = cfg.with_mosa(sparsity=32, n_mosa_heads=cfg.attention.n_heads,
                            local_window=4096, k_fixed=512)
        note = "mosa_hybrid long-context mode (paper §3.4): " \
               "k_fixed=512, local window 4096"
    elif mosa:
        cfg = cfg.with_mosa(sparsity=32,
                            n_mosa_heads=4 * cfg.attention.n_heads)
        note = "mosa_hybrid variant (paper technique): rho=32, " \
               f"{4 * cfg.attention.n_heads} sparse + 4 dense heads"
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, remat=remat or "full")
    elif remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    return cfg, shape, note


def build_model(cfg, mesh, rule_set: str, act_seq_shard: bool):
    act_spec = None
    if act_seq_shard:
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        act_spec = P(dp if dp else None, "model")
    return TransformerLM(cfg, act_spec=act_spec)


def lower_cell(arch: str, shape_name: str, mesh, rule_set: str = "fsdp_tp",
               act_seq_shard: bool = True, mosa: bool = False,
               remat: str | None = None, use_hints: bool = True):
    cfg, shape, note = build_cfg(arch, shape_name, mosa=mosa, remat=remat)
    model = build_model(cfg, mesh, rule_set,
                        act_seq_shard and shape.kind == "train")
    shapes = init_shapes(model)
    param_sh = shd.param_shardings(model, mesh, rule_set, shapes)
    specs = input_specs(cfg, shape)
    batch_sh = shd.batch_sharding(mesh, rule_set, batch=shape.global_batch)
    emb_sh = NamedSharding(mesh, P(*(batch_sh.spec + (None,))))

    def in_sh(spec_dict):
        return {k: emb_sh if k == "embeds" else batch_sh
                for k in spec_dict}

    import contextlib
    hint_ctx = hints.sharding_hints(mesh=mesh) if use_hints else \
        contextlib.nullcontext()
    with mesh, hint_ctx:
        if shape.kind == "train":
            opt = adamw(schedules.linear_warmup(2.5e-4, 400), clip_norm=0.25)
            opt_shapes = jax.eval_shape(opt.init, shapes)
            opt_sh = {"mu": param_sh, "nu": param_sh}

            def train_step(params, opt_state, step, batch):
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(params, batch)
                updates, opt_state, _ = opt.update(grads, opt_state, params,
                                                   step)
                params = apply_updates(params, updates)
                return params, opt_state, step + 1, loss

            lowered = jax.jit(
                train_step,
                in_shardings=(param_sh, opt_sh, None, in_sh(specs)),
                out_shardings=(param_sh, opt_sh, None, None),
                donate_argnums=(0, 1),
            ).lower(shapes, opt_shapes,
                    jax.ShapeDtypeStruct((), jnp.int32), specs)

        elif shape.kind == "prefill":
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = shd.cache_shardings(cache_shapes, mesh, rule_set,
                                           seq_sharded=shape.global_batch == 1)

            def prefill_step(params, batch, caches):
                tokens = batch.get("tokens")
                embeds = batch.get("embeds")
                return model.prefill(params, tokens, caches,
                                     inputs_embeds=embeds)

            pf_specs = {k: v for k, v in specs.items() if k != "labels"}
            lowered = jax.jit(
                prefill_step,
                in_shardings=(param_sh, in_sh(pf_specs), cache_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(2,),
            ).lower(shapes, pf_specs, cache_shapes)

        else:  # decode
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = shd.cache_shardings(cache_shapes, mesh, rule_set,
                                           seq_sharded=shape.global_batch == 1)

            def serve_step(params, token, caches):
                return model.decode_step(params, token, caches)

            lowered = jax.jit(
                serve_step,
                in_shardings=(param_sh, batch_sh, cache_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(2,),
            ).lower(shapes, specs["token"], cache_shapes)

    return lowered, cfg, shape, note, model


def _trip_counts(model, shape):
    """(layer-scan trips, inner-loop trips, inner-inner) for collective
    correction.  Inner trips are the chunked-scan counts of the mixers."""
    head, p, units, tail_start, pattern = model._layout()
    if shape.kind == "decode":
        return (max(units, 1), 1, 1)
    T = shape.seq_len
    inner = 1
    kinds = {b.mixer for b in pattern}
    if kinds & {"attn", "attn_local", "mosa"}:
        inner = max(inner, -(-T // 512))        # chunked attention
    if "mamba" in kinds:
        inner = max(inner, -(-T // 128))        # mamba chunk scan
    if "mlstm" in kinds:
        inner = max(inner, -(-T // 64))
    inner2 = 128 if (kinds & {"mamba", "mlstm"}) else 1
    if "slstm" in kinds:
        inner = max(inner, T)                   # per-token recurrence
    return (max(units, 1), inner, inner2)


def analyze(lowered, compiled, n_devices: int, trip_counts=(1,),
            cfg=None, shape=None):
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    coll = parse_collective_bytes(compiled.as_text(), trip_counts)
    per_dev_flops = float(ca.get("flops", 0.0))
    per_dev_bytes = float(ca.get("bytes accessed", 0.0))
    mem = {}
    if ma is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            mem[f] = int(getattr(ma, f, 0))
        mem["total_per_device"] = (mem["argument_size_in_bytes"] +
                                   mem["temp_size_in_bytes"] +
                                   mem["output_size_in_bytes"])
    rec = {
        "n_devices": n_devices,
        # NOTE: cost_analysis counts while(scan) bodies once — these raw HLO
        # numbers are diagnostics; the roofline uses the analytic block.
        "per_device_flops_hlo_raw": per_dev_flops,
        "per_device_bytes_hlo_raw": per_dev_bytes,
        "collective_bytes_per_device": coll,
        "memory": mem,
    }
    if cfg is not None and shape is not None:
        from benchmarks.analytic import cell_cost
        cc = cell_cost(cfg, shape)
        rec["analytic"] = {
            "flops_global": cc.flops_global,
            "bytes_global": cc.bytes_global,
            "model_flops": cc.model_flops,
            "n_params": cc.n_params,
            "n_active": cc.n_active,
        }
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             rule_set: str = "fsdp_tp", out_dir: str = "experiments/dryrun",
             act_seq_shard: bool = True, tag: str = "", mosa: bool = False,
             remat: str | None = None, use_hints: bool = True):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = 512 if multi_pod else 256
    t0 = time.time()
    lowered, cfg, shape, note, model = lower_cell(arch, shape_name, mesh,
                                                  rule_set, act_seq_shard,
                                                  mosa=mosa, remat=remat,
                                                  use_hints=use_hints)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    trips = _trip_counts(model, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "rule_set": rule_set, "model_name": cfg.name, "note": note,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        **analyze(lowered, compiled, n_dev, trips, cfg, shape),
    }
    sub = os.path.join(out_dir, mesh_name + (f"_{tag}" if tag else ""))
    os.makedirs(sub, exist_ok=True)
    with open(os.path.join(sub, f"{arch}__{shape_name}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    mem = rec["memory"].get("total_per_device", 0) / 2**30
    print(f"[ok] {arch:24s} {shape_name:12s} {mesh_name}  "
          f"compile {t_compile:6.1f}s  mem/dev {mem:7.2f} GiB  "
          f"flops/dev {rec['analytic']['flops_global']/n_dev:.3e}  "
          f"coll/dev {rec['collective_bytes_per_device']['total']/2**20:9.1f} MiB")
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--rule-set", default="fsdp_tp")
    p.add_argument("--no-act-shard", action="store_true")
    p.add_argument("--out-dir", default="experiments/dryrun")
    p.add_argument("--tag", default="")
    p.add_argument("--mosa", action="store_true",
                   help="apply the paper's MoSA hybrid to the arch")
    p.add_argument("--remat", default=None,
                   choices=[None, "full", "dots_saveable", "none"])
    p.add_argument("--no-hints", action="store_true")
    args = p.parse_args(argv)

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    run_cell(arch, shape, multi_pod=mp,
                             rule_set=args.rule_set, out_dir=args.out_dir,
                             act_seq_shard=not args.no_act_shard,
                             tag=args.tag, mosa=args.mosa, remat=args.remat,
                             use_hints=not args.no_hints)
                except Exception as e:
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"[FAIL] {arch} {shape} multi_pod={mp}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall cells compiled OK")


if __name__ == "__main__":
    main()
