"""Chip smoke test: the serving and training main paths on a TPU, at the
paper's medium width.

Run from the repository root, with no environment set:

    python chip_smoke.py             # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4   # four chips: sharded train steps only

Model: ``mosa-paper`` size ``medium``, hybrid MoSA at sparsity 8 and
T = 1024 — 18 layers, d_model 1024, d_ff 4096, vocab 8000, 4 dense + 54
MoSA heads per layer, d_head 64, k = 128; about 442M parameters, random
weights from a seed.  Neither width nor depth is cut.

Phases (one process; any failed check exits non-zero before the last line):

  * device check — the first device must be a TPU;
  * serve — ``Server`` with paged caches + ``Scheduler`` serve 16 seeded
    requests (prompts of 128–900 tokens, 32 greedy tokens each) in bf16.
    Every request must finish with exactly 32 tokens and every logit the
    serving programs sampled from must be finite.  Parity: the shortest
    prompt, served again alone with the prefix cache off (so one 512-token
    chunk prefills it), must follow the contiguous ``Server`` path
    (``prefill`` then ``decode_step``, fed the same tokens) for 8 tokens:
    each must be within ``PARITY_TOL`` logit standard deviations of that
    path's best logit (0 when the greedy tokens agree exactly).  With the
    prefix cache on, every prompt is prefilled in two chunks, and
    token-choice MoSA layers after the first see a chunk-causal selection
    there, so one-shot parity is not expected;
  * train — ``Trainer`` with the fused Pallas MoSA kernels (fwd + bwd),
    bf16 compute / fp32 master weights; every step's loss must be finite;
  * native kernels — the compiled packed-prefill, ``decode_many`` and
    train-step programs must contain ``tpu_custom_call``: no interpreter
    and no reference path stood in for a Pallas kernel.

``--chips 4`` runs two train steps on a (2, 2) data x model mesh under the
``fsdp_tp`` rule set and the same two steps on a one-device mesh, requires
the losses to agree within ``SHARDED_LOSS_TOL``, and prints each chip's
memory in use to show the state is spread over the chips.

Times printed on the way are smoke observations of one run, not metrics.
The last line of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = dict(size="medium", variant="mosa", sparsity=8)
SEQ = 1024
SEED = 0
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 16, (128, 900), 32
SERVE_BATCH, BLOCK = 8, 16
PARITY_STEPS = 8
# A greedy token may differ from the contiguous path's only at a near-tie:
# bf16 rounding moves logits by ~1% of their spread, while a wrong token
# from a broken path sits ~3.5 standard deviations below the best logit.
PARITY_TOL = 0.2
# Training batch: B=8 at T=1024 needs 28.1 GiB of HBM without remat
# (compile-time estimate for one v5e chip, 15.75 GiB); B=2 needs 14.6 GiB.
TRAIN_BATCH, TRAIN_STEPS = 2, 4
SHARDED_STEPS = 2
COMPUTE = "bfloat16"
# Same two steps, one device vs a (2, 2) mesh.  Sharded, bf16 activations
# round at other points and expert-choice selection flips on near-ties:
# on a 2x2 v5e host the losses (~9.4 at random init) differed by 3.3e-3 and
# 3.0e-2; on four virtual CPU devices at a small width they differed by
# 0.008 and 0.02 in bf16 and not at all in fp32 compute.
SHARDED_LOSS_TOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def native_kernels(name: str, jitted, *args) -> None:
    """Compile ``jitted`` for ``args`` and require a Pallas TPU kernel."""
    t0 = time.perf_counter()
    text = jitted.lower(*args).compile().as_text()
    check("tpu_custom_call" in text,
          f"{name}: no tpu_custom_call in the compiled program")
    say(f"native kernels in {name}: yes (compile {time.perf_counter() - t0:.1f}"
        f" s, smoke observation)")


def serve_phase(cfg, mesh) -> None:
    from repro.dist import hints
    from repro.launch.serve import Server
    from repro.serve import Scheduler
    from repro.serve.paged_kv import PagedConfig

    nb = SEQ // BLOCK
    server = Server(cfg, mesh=mesh, batch=SERVE_BATCH, max_len=SEQ,
                    paged=PagedConfig(block_size=BLOCK,
                                      num_blocks=SERVE_BATCH * nb,
                                      num_window_blocks=4 * SERVE_BATCH))
    sched = Scheduler(server, chunk=1, chunk_tokens=512, max_prefill_segs=4)
    C, N = sched.chunk_tokens, sched.max_segs
    # The Server's fused decoder with the logits it samples from as an
    # extra output (``return_logits``), so their finiteness can be checked.
    decode_many = jax.jit(
        server.model.decode_many, static_argnums=(4, 6, 7),
        in_shardings=(server.param_sh, None, server.cache_sh, None, None),
        out_shardings=(None, None, server.cache_sh), donate_argnums=(2,))
    with mesh, hints.sharding_hints(mesh=mesh):
        native_kernels("packed prefill", server._prefill_packed_jit,
                       server.params, jnp.zeros((1, C), jnp.int32),
                       sched.caches, jnp.zeros((N + 1,), jnp.int32),
                       jnp.full((N,), -1, jnp.int32),
                       jnp.zeros((N,), jnp.int32))
        native_kernels("decode_many", decode_many, server.params,
                       jnp.zeros((SERVE_BATCH, 1), jnp.int32), sched.caches,
                       jax.random.PRNGKey(0), 1, jnp.float32(0.0), 0, True)

    # Every logit the serving programs sample from feeds one list of
    # on-device finiteness flags, read once after the run.
    finite = []
    prefill_packed = server.prefill_packed

    def prefill_checked(*args):
        logits, caches = prefill_packed(*args)
        finite.append(jnp.isfinite(logits).all())
        return logits, caches

    def decode_checked(params, tok, caches, key, n, temperature=0.0,
                       top_k=0):
        toks, logits, caches = decode_many(params, tok, caches, key, n,
                                           jnp.float32(temperature), top_k,
                                           True)
        finite.append(jnp.isfinite(logits).all())
        return toks, caches

    server.prefill_packed = prefill_checked
    server.decode_many = decode_checked

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    prompts = [rng.integers(2, cfg.vocab, n).astype(np.int32) for n in lens]
    for p in prompts:
        sched.submit(p, NEW_TOKENS)
    t0 = time.perf_counter()
    results = sched.run(max_steps=100_000)
    wall = time.perf_counter() - t0
    check(len(results) == N_REQUESTS,
          f"serve: {len(results)} of {N_REQUESTS} requests finished")
    for rid, toks in results.items():
        check(len(toks) == NEW_TOKENS,
              f"serve: request {rid} returned {len(toks)} tokens")
    ttft = sorted(r["ttft_s"] for r in sched.records.values())
    say(f"serve: {N_REQUESTS} requests x {NEW_TOKENS} tokens, prompts "
        f"{lens.min()}-{lens.max()} tokens, all finished "
        f"(prefix hits {sched.stats['prefix_hits']}, prefill chunks "
        f"{sched.stats['prefill_chunks']})")
    say(f"serve wall {wall:.2f} s incl. compiles, TTFT median "
        f"{ttft[len(ttft) // 2]:.3f} s max {ttft[-1]:.3f} s "
        f"(smoke observations)")
    del sched

    # Parity: one prompt prefilled in one chunk vs the contiguous path,
    # fed the paged path's tokens one at a time.
    prompt = prompts[int(np.argmin(lens))]
    check(len(prompt) <= C, f"serve: parity prompt of {len(prompt)} tokens")
    solo = Scheduler(server, chunk=1, chunk_tokens=C, max_prefill_segs=N,
                     prefix_cache=False)
    rid = solo.submit(prompt, PARITY_STEPS)
    got = np.asarray(solo.run()[rid])
    check(len(got) == PARITY_STEPS, f"serve: parity run gave {len(got)}")
    check(bool(jnp.stack(finite).all()), "serve: non-finite logits")
    say(f"serve: {len(finite)} serving dispatches, all logits finite")
    del solo
    ref = Server(cfg, mesh=mesh, batch=1, max_len=SEQ, params=server.params)
    with mesh, hints.sharding_hints(mesh=mesh):
        logits, caches = ref.prefill(ref.params, jnp.asarray(prompt)[None],
                                     ref.new_cache())
        margins, exact = [], 0
        for j in range(PARITY_STEPS):
            row = np.asarray(logits[0, -1], np.float32)
            margins.append(float(row.max() - row[got[j]]) / float(row.std()))
            exact += int(row.argmax() == got[j])
            logits, caches = ref.decode_step(
                ref.params, jnp.asarray([[got[j]]], jnp.int32), caches)
    say(f"serve parity ({len(prompt)}-token prompt, {PARITY_STEPS} tokens, "
        f"paged Scheduler vs contiguous Server): {exact}/{PARITY_STEPS} "
        f"greedy tokens agree "
        f"exactly; max logit margin {max(margins):.4f} std "
        f"(tolerance {PARITY_TOL} std)")
    check(max(margins) <= PARITY_TOL, "serve: parity margin over tolerance")


def train_config(**kw):
    from repro.train.loop import TrainConfig
    return TrainConfig(arch="mosa-paper", preset="full", arch_kwargs=ARCH,
                       seq_len=SEQ, global_batch=TRAIN_BATCH,
                       mosa_impl="pallas", compute=COMPUTE, log_every=1,
                       **kw)


def abstract_train_args(trainer):
    from repro.nn.module import init_shapes
    shapes = init_shapes(trainer.model)
    tok = jax.ShapeDtypeStruct((TRAIN_BATCH, SEQ), jnp.int32)
    return (shapes, jax.eval_shape(trainer.optimizer.init, shapes),
            jax.ShapeDtypeStruct((), jnp.int32),
            {"tokens": tok, "labels": tok})


def run_trainer(trainer, check_kernels: bool):
    from repro.dist import hints
    if check_kernels:
        with trainer.mesh, hints.sharding_hints(mesh=trainer.mesh):
            native_kernels("train step", trainer.train_step,
                           *abstract_train_args(trainer))
    params, opt, history = trainer.run(install_signals=False)
    losses = [h["loss"] for h in history]
    check(len(losses) == trainer.cfg.steps and all(map(np.isfinite, losses)),
          f"train: losses {losses}")
    return params, opt, history


def train_phase() -> None:
    from repro.train.loop import Trainer
    say(f"train: batch {TRAIN_BATCH} x {SEQ} tokens (cut from 8 to fit one "
        f"chip's HBM without remat; width and depth uncut)")
    trainer = Trainer(train_config(steps=TRAIN_STEPS, mesh_shape=(1, 1)))
    _, _, history = run_trainer(trainer, check_kernels=True)
    say("train: losses " + ", ".join(f"{h['loss']:.4f}" for h in history)
        + " — all finite")
    say("train step times " + ", ".join(f"{h['dt']:.3f}" for h in history)
        + " s (step 0 includes compile; smoke observations)")


def sharded_phase() -> None:
    """Two train steps on a (2, 2) mesh vs the same steps on one device."""
    from repro.train.loop import Trainer
    runs = {}
    for shape in ((1, 1), (2, 2)):
        trainer = Trainer(train_config(steps=SHARDED_STEPS, mesh_shape=shape,
                                       rule_set="fsdp_tp", warmup=1))
        params, opt, history = run_trainer(trainer,
                                           check_kernels=shape == (2, 2))
        runs[shape] = [h["loss"] for h in history]
        say(f"mesh {shape}: losses " + ", ".join(f"{x:.5f}" for x in
                                                 runs[shape]))
        if shape == (2, 2):
            per_dev = {d.id: 0 for d in jax.devices()[:4]}
            total = 0
            for leaf in jax.tree.leaves(params):
                total += leaf.nbytes
                for s in leaf.addressable_shards:
                    per_dev[s.device.id] += s.data.nbytes
            for d in jax.devices()[:4]:
                stats = d.memory_stats() or {}
                say(f"device {d.id}: bytes_in_use "
                    f"{stats.get('bytes_in_use')}, parameter bytes "
                    f"{per_dev[d.id]} of {total}")
            check(max(per_dev.values()) < total / 2,
                  "sharded: parameters are not spread over the chips")
        del params, opt, trainer
        gc.collect()
    diff = max(abs(a - b) for a, b in zip(runs[(1, 1)], runs[(2, 2)]))
    say(f"sharded vs one device: max |loss difference| {diff:.2e} "
        f"(tolerance {SHARDED_LOSS_TOL})")
    check(diff <= SHARDED_LOSS_TOL, "sharded: losses disagree")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    say(f"device: {dev.device_kind}, {count} device(s), jax {jax.__version__}")
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1

    from repro.configs.base import get_config
    from repro.dist import sharding as shd
    from repro.launch.compile_cache import use_compile_cache
    from repro.nn.module import init_shapes
    from repro.nn.transformer import TransformerLM

    say(f"compile cache: {use_compile_cache()}")
    cfg = get_config("mosa-paper", preset="full", seq_len=SEQ,
                     dtype="bfloat16", **ARCH)
    n_params = sum(x.size for x in jax.tree.leaves(
        init_shapes(TransformerLM(cfg))))
    m = cfg.mosa
    say(f"model: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{m.n_dense_heads} dense + {m.n_mosa_heads} MoSA heads, d_head "
        f"{m.d_head}, {n_params / 1e6:.1f}M parameters")

    try:
        if args.chips == 4:
            sharded_phase()
        else:
            serve_phase(cfg, shd.make_mesh((1, 1), ("data", "model")))
            gc.collect()
            train_phase()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
