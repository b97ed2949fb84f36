"""Serving launcher: paged continuous batching, optionally model-parallel.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 16 --new-tokens 16

  # sharded decode over whatever local devices exist (e.g. 8 CPU devices
  # under XLA_FLAGS=--xla_force_host_platform_device_count=8):
  ... --mesh 4x2

  # speculative decoding (device-side n-gram drafting + batched paged
  # verify; greedy-only, the same tokens as plain decode):
  ... --speculate 4
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_arch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.serve import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size (default: PACO leaf tile)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="page-pool size (default: slots*max_seq/page; "
                         "smaller values exercise preemption)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="prefill chunk length (jitted tokens per call)")
    ap.add_argument("--ticks-per-dispatch", type=int, default=8,
                    help="decode steps fused into one jitted dispatch "
                         "(default 8).  Throughput/latency tradeoff: each "
                         "dispatch runs N steps on-device and syncs ONE "
                         "(N, slots) token block to the host, so larger N "
                         "amortizes dispatch + host-sync overhead over "
                         "more tokens (higher tok/s) but delays token "
                         "visibility and admission/retirement decisions "
                         "by up to N ticks and speculatively maps up to "
                         "N positions of pages per slot (more preemption "
                         "under a tight pool).  1 = lowest latency, "
                         "per-token scheduling.")
    ap.add_argument("--speculate", type=int, default=None,
                    help="draft length for speculative decoding: each "
                         "dispatch step drafts N continuation tokens per "
                         "slot from its own history (device-side n-gram "
                         "lookup, no draft model), verifies the window "
                         "in ONE batched forward, and keeps the greedy-"
                         "correct prefix — up to N+1 tokens per model "
                         "pass, the same greedy tokens.  0 plans the "
                         "window as a PACO leaf tile of the cache "
                         "cuboid.  Greedy-only (default sampler).")
    ap.add_argument("--spec-min-accept", type=float, default=0.25,
                    help="adaptive-fallback threshold: when the rolling "
                         "draft-acceptance rate of the last 32 verify "
                         "windows drops below this, dispatch plain "
                         "fused decode instead (speculative probe every "
                         "16th dispatch).  Break-even acceptance is "
                         "backend-dependent; 0 disables the fallback.")
    ap.add_argument("--verify-parity", action="store_true",
                    help="after the drain, re-decode every request "
                         "through serve.reference (dense per-token "
                         "oracle) and assert token-exact parity — slow, "
                         "meant for smoke tests at reduced scale")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL host mesh, e.g. 4x2 (default: none)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.lower().split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(params, cfg, slots=args.slots,
                         max_seq=args.max_seq, page_size=args.page_size,
                         pool_pages=args.pool_pages,
                         prefill_chunk_len=args.chunk, mesh=mesh,
                         ticks_per_dispatch=args.ticks_per_dispatch,
                         speculate=args.speculate,
                         spec_min_accept=args.spec_min_accept)
    print(f"{cfg.name}: slots={args.slots} page={engine.page} "
          f"chunk={engine.chunk} pool={engine.pool.n_pages} pages "
          f"ticks/dispatch={engine.ticks}"
          + (f" draft_len={engine.draft_len}"
             if engine.draft_len is not None else "")
          + (f" mesh={dict(mesh.shape)}" if mesh else ""))
    for i in range(args.requests):
        engine.submit(Request(uid=i, prompt=[1 + i % 7, 2, 3 + i % 5],
                              max_new_tokens=args.new_tokens))
    t0 = time.perf_counter()
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0
    engine.check_page_invariants()
    total = sum(len(r.out) for r in done)
    chunk = engine.chunk
    budget_ok = all(
        r.prefill_calls <= (r.preemptions + 1)
        * -(-(len(r.prompt) + len(r.out)) // chunk) for r in done)
    print(f"served {len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s); prefill calls="
          f"{engine.stats['prefill_calls']} (<=ceil(len/chunk) per admit: "
          f"{'ok' if budget_ok else 'VIOLATED'}), decode steps="
          f"{engine.stats['decode_steps']} in "
          f"{engine.stats['dispatches']} dispatches "
          f"({engine.stats['host_syncs']} host syncs), "
          f"preemptions={engine.stats['preemptions']}")
    if engine.draft_len is not None:
        s = engine.stats
        rate = s["accepted_tokens"] / max(s["drafted_tokens"], 1)
        per_win = s["decode_tokens"] / max(s["spec_windows"], 1)
        print(f"speculation: draft_len={engine.draft_len} "
              f"windows={s['spec_windows']} "
              f"accepted={s['accepted_tokens']}/{s['drafted_tokens']} "
              f"drafts (rate={rate:.2f}), "
              f"tokens/window={per_win:.2f}, decode tokens/sync="
              f"{s['decode_tokens'] / max(s['dispatches'], 1):.1f}, "
              f"fallback dispatches={s['spec_fallback_dispatches']}")
    for r in done[:4]:
        print(f"  req {r.uid}: {r.out[:8]}")
    if args.verify_parity:
        from repro.serve import reference_decode
        for r in sorted(done, key=lambda r: r.uid):
            ref = reference_decode(params, cfg, r.prompt,
                                   max_new_tokens=r.max_new_tokens,
                                   eos_id=r.eos_id,
                                   max_seq=engine.max_seq)
            assert r.out == ref, (
                f"req {r.uid}: engine {r.out} != reference {ref}")
        print(f"reference parity: ok ({len(done)} requests)")


if __name__ == "__main__":
    main()
