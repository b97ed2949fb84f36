#!/usr/bin/env python3
"""Smoke run of the serving path on TPU: qwen3-0.6b at its published
widths and depth (28 layers, d_model 1024, 16/8 heads, head_dim 128,
vocab 151936, bf16), random weights from ``--seed``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # ServeEngine over a 1x4 mesh against
                                      # the one-chip engine on device 0

The one-chip run builds ``ServeEngine(params, cfg, slots=16,
max_seq=2048)`` with the default page plan, prefill chunk and ticks per
dispatch, compiles its step programs ahead of time (printing compile
seconds and ``memory_analysis()``), drains 24 seeded requests (prompts of
128-1024 random tokens, 64 new tokens each) once to warm every shape and
once more timed, checks the page invariants, and checks tokens against a
float32 teacher-forced ``serve.reference.forward_ref`` (see
``reference_check``).  The four-chip run serves the same requests through
``ServeEngine(mesh=...)`` built as ``launch.serve --mesh 1x4`` builds it,
then through the one-chip engine, and compares both and the reference.

Everything runs in this one process.  Printed numbers are smoke numbers
from a single run, not benchmark results.  The last line of stdout is
``{"ok": true, "device": {...}}``; any failure exits non-zero without it,
and so does a run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen3-0.6b"
SLOTS, MAX_SEQ = 16, 2048
N_REQUESTS, NEW_TOKENS = 24, 64
PROMPT_LENS = (128, 1024)
N_CHECKED = 4          # requests re-scored by the reference
# A position is exempt from the token check when the reference's
# top-1/top-2 logit gap there is below TAU.  An engine token can differ
# from the reference argmax only where that gap is under twice the
# largest engine-vs-reference logit error.  The bf16 forward against the
# float32 (precision "highest") reference at width 1024 (4, 8 and 16
# layers, vocab cut to 8192, 256 positions, CPU) erred by at most 0.083,
# so flips need a gap under ~0.17; TAU is three times that.
TAU = 0.5
MAX_EXEMPT = 0.05      # more exempt positions than this: check is vacuous


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def memory_line(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    return " | ".join(
        f"dev{d.id} in_use={s.get('bytes_in_use', 0) / 2**30:.3f}GiB "
        f"peak={s.get('peak_bytes_in_use', 0) / 2**30:.3f}GiB "
        f"limit={s.get('bytes_limit', 0) / 2**30:.3f}GiB"
        for d, s in zip(devices, stats))


def make_prompts(seed: int, vocab: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, vocab, n).tolist() for n in lens]


def compile_steps(engine) -> None:
    """AOT-compile the engine's step programs at their widest shapes and
    print compile seconds and what the compiler says they hold."""
    for name, lowered in engine.lower_steps().items():
        t0 = time.perf_counter()
        m = lowered.compile().memory_analysis()
        dt = time.perf_counter() - t0
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        say(f"compile {name}: {dt:.2f}s  argument="
            f"{m.argument_size_in_bytes / 2**30:.3f}GiB output="
            f"{m.output_size_in_bytes / 2**30:.3f}GiB temp="
            f"{m.temp_size_in_bytes / 2**30:.3f}GiB alias="
            f"{m.alias_size_in_bytes / 2**30:.3f}GiB total={total / 2**30:.3f}"
            f"GiB (per device)")


def drain(engine, prompts, *, timed: bool) -> dict[int, list[int]]:
    """Serve every prompt to completion.  The warm-up drain is
    ``run_until_drained()``, as the launcher calls it; the timed drain
    steps ``tick()`` (the loop ``run_until_drained`` runs) to stamp each
    request's first token."""
    from repro.serve import Request

    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    before = dict(engine.stats)
    first: dict[int, float] = {}
    t0 = time.perf_counter()
    if timed:
        while engine.queue or any(r is not None for r in engine.active):
            engine.tick()
            now = time.perf_counter() - t0
            for r in reqs:
                if r.out and r.uid not in first:
                    first[r.uid] = now
    else:
        engine.run_until_drained()
    wall = time.perf_counter() - t0
    if len(engine.done) < len(reqs) or any(len(r.out) != NEW_TOKENS
                                           for r in reqs):
        fail(f"drain left requests unfinished: "
             f"{[(r.uid, len(r.out)) for r in reqs]}")
    engine.check_page_invariants()
    if engine.pool.free_count() != engine.pool.n_pages:
        fail(f"pages leaked: {engine.pool.free_count()} free of "
             f"{engine.pool.n_pages} after the drain")
    d = {k: engine.stats[k] - before[k] for k in engine.stats}
    kind = "timed" if timed else "warm-up (includes compiles)"
    say(f"drain {kind}: {len(reqs)} requests, {d['prefill_tokens']} "
        f"prompt + {sum(len(r.out) for r in reqs)} new tokens in "
        f"{wall:.2f}s; page invariants hold, all pages returned")
    if timed:
        ttft = sorted(first.values())
        say(f"  time to first token from submission (closed loop, "
            f"{len(reqs)} requests on {engine.slots} slots): median "
            f"{statistics.median(ttft):.3f}s max {ttft[-1]:.3f}s")
        say(f"  decode {d['decode_tokens']} tokens in {d['decode_s']:.3f}s "
            f"= {d['decode_tokens'] / d['decode_s']:.1f} tok/s; prefill "
            f"{d['prefill_tokens']} tokens in {d['prefill_s']:.3f}s")
    say(f"  engine.stats (cumulative) {engine.stats}")
    return {r.uid: list(r.out) for r in reqs}


def reference_scores(params, cfg):
    """Jitted float32 teacher-forced reference (``forward_ref`` under
    matmul precision "highest"; on TPU a float32 matmul otherwise takes a
    single bf16 pass).  Returns a function (prompt, out) -> reference
    logits (NEW_TOKENS, vocab) at the positions that predicted ``out``.
    Every call pads to one length, so it compiles once; padding sits
    after the scored positions, which causal attention never reads."""
    import jax
    import jax.numpy as jnp

    from repro.serve.reference import forward_ref

    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    cfg32 = dataclasses.replace(cfg, param_dtype="float32")
    length = PROMPT_LENS[1] + NEW_TOKENS

    @jax.jit
    def scores(p, tokens, pos):
        return forward_ref(p, cfg32, tokens)[0, pos]

    def run(prompt, out):
        seq = prompt + out[:-1]
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        pos = len(prompt) - 1 + np.arange(len(out))
        with jax.default_matmul_precision("highest"):
            return np.asarray(scores(p32, tokens, pos))[:, :cfg.vocab]

    return run


def reference_check(ref, prompts, outs: dict[int, list[int]],
                    uids) -> None:
    """Each engine token must equal the reference argmax at its
    position, except where the reference's top-1/top-2 gap is below
    TAU; fails when more than MAX_EXEMPT of the positions are exempt."""
    n = exempt = flips = echo = 0
    worst = np.inf
    for uid in uids:
        logits = ref(prompts[uid], outs[uid])
        top2 = np.sort(logits, axis=-1)[:, -2:]
        gap = top2[:, 1] - top2[:, 0]
        want = logits.argmax(-1)
        got = np.asarray(outs[uid])
        prev = np.asarray(([prompts[uid][-1]] + outs[uid])[:-1])
        ok = (got == want) | (gap < TAU)
        if not ok.all():
            i = int(np.argmin(ok))
            fail(f"request {uid} token {i}: engine {got[i]} != reference "
                 f"{want[i]} with reference gap {gap[i]:.4f} >= TAU={TAU}")
        n += len(got)
        exempt += int((gap < TAU).sum())
        flips += int((got != want).sum())
        echo += int((want == prev).sum())
        worst = min(worst, float(gap.min()))
    say(f"reference check: {n} positions of {len(uids)} requests, "
        f"{n - flips} equal the float32 argmax, {exempt} exempt "
        f"(gap < TAU={TAU}; {flips} of them differ), smallest gap "
        f"{worst:.4f}; reference argmax repeats the input token at "
        f"{echo}/{n} positions (tied embeddings, random weights)")
    if exempt > MAX_EXEMPT * n:
        fail(f"{exempt}/{n} positions exempt exceeds {MAX_EXEMPT:.0%}: "
             f"the token check would be vacuous")


def build_engine(params, cfg, mesh=None):
    from repro.serve import ServeEngine

    t0 = time.perf_counter()
    engine = ServeEngine(params, cfg, slots=SLOTS, max_seq=MAX_SEQ,
                         mesh=mesh)
    pool_bytes = sum(x.nbytes for x in engine.pool.pools.values())
    say(f"engine: slots={engine.slots} max_seq={engine.max_seq} "
        f"page={engine.page} pages={engine.pool.n_pages} chunk="
        f"{engine.chunk} ticks/dispatch={engine.ticks} pool="
        f"{pool_bytes / 2**30:.3f}GiB built in "
        f"{time.perf_counter() - t0:.2f}s"
        + (f" mesh={dict(mesh.shape)}" if mesh is not None else ""))
    return engine


def one_chip(params, cfg, prompts, devices) -> None:
    engine = build_engine(params, cfg)
    say(f"memory after engine build: {memory_line(devices)}")
    compile_steps(engine)
    drain(engine, prompts, timed=False)
    outs = drain(engine, prompts, timed=True)
    say(f"memory after drains: {memory_line(devices)}")
    del engine
    gc.collect()
    ref = reference_scores(params, cfg)
    reference_check(ref, prompts, outs, range(N_CHECKED))


def four_chips(params, cfg, prompts, devices) -> None:
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 4), ("data", "model"))
    engine = build_engine(params, cfg, mesh=mesh)
    say(f"memory after mesh engine build: {memory_line(devices)}")
    compile_steps(engine)
    sharded = drain(engine, prompts, timed=False)
    say(f"memory after mesh drain: {memory_line(devices)}")
    del engine
    gc.collect()
    engine = build_engine(params, cfg)
    single = drain(engine, prompts, timed=False)
    del engine
    gc.collect()
    ref = reference_scores(params, cfg)
    reference_check(ref, prompts, sharded, range(N_CHECKED))
    # tokens agree until a request's first difference, which must sit
    # at a reference near-tie; after it the two contexts differ, so the
    # rest of that request is not compared.
    diverged = 0
    for uid in range(len(prompts)):
        diff = [i for i, (a, b) in enumerate(zip(sharded[uid],
                                                 single[uid])) if a != b]
        if not diff:
            continue
        i = diff[0]
        logits = ref(prompts[uid], single[uid])
        top2 = np.sort(logits[i])[-2:]
        if top2[1] - top2[0] >= TAU:
            fail(f"request {uid} token {i}: mesh {sharded[uid][i]} != "
                 f"one-chip {single[uid][i]} with reference gap "
                 f"{top2[1] - top2[0]:.4f} >= TAU={TAU}")
        diverged += NEW_TOKENS - i
    n = len(prompts) * NEW_TOKENS
    say(f"mesh vs one-chip engine: {n - diverged}/{n} tokens equal; "
        f"{diverged} follow a near-tie divergence")
    if diverged > MAX_EXEMPT * n:
        fail(f"{diverged}/{n} tokens past near-tie divergences exceeds "
             f"{MAX_EXEMPT:.0%}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"no {SRC}/repro: run this script from a checkout of the "
             f"repository")
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {devices[0].platform!r}); this "
             f"smoke run never falls back to another backend")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU devices, JAX "
             f"finds {len(devices)}")
    devices = devices[:args.chips]

    from repro.configs import get_arch
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import init_params, param_count

    say(f"compile cache: {use_compile_cache()}")
    say(f"device: {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__}); smoke numbers from one run, not "
        f"benchmark results")
    cfg = get_arch(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(cfg, jax.random.PRNGKey(args.seed)))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    say(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"vocab={cfg.vocab} dtype={cfg.param_dtype}; "
        f"{param_count(params)} params = {n_bytes / 2**30:.3f}GiB, "
        f"random init (seed {args.seed}) in "
        f"{time.perf_counter() - t0:.2f}s")
    prompts = make_prompts(args.seed, cfg.vocab)
    (one_chip if args.chips == 1 else four_chips)(params, cfg, prompts,
                                                  devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
