"""Batched serving demo: prefill + decode with KV cache, continuous batching,
and the sparse-serving path (activation clipping live at decode).

``--trace poisson|mmpp`` replaces the fixed request list with the request
*mix* of a seeded simulator trace (``repro.sim.trace``) — the same
request counts and decode-length buckets the deployment simulator scores
analytically (DESIGN.md §13). The replay is closed-loop (back to back):
arrival-time burstiness only matters under open-loop admission, which is
the simulator's job, not this CPU demo's.

    PYTHONPATH=src python examples/serve_batched.py
    PYTHONPATH=src python examples/serve_batched.py --trace mmpp
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.models import build_model
from repro.serve.serve_loop import ServeSession


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--trace", choices=["poisson", "mmpp"], default=None,
                    help="drive the session from a seeded simulator trace "
                         "instead of a fixed request list")
    args = ap.parse_args()

    cfg = reduce_config(get_config(args.arch))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))

    sess = ServeSession(api, params, batch_slots=args.batch_slots,
                        S_max=args.prompt_len + args.max_new + 8)
    if args.trace:
        from repro.sim.trace import mmpp_trace, poisson_trace
        sizes = ((8, args.max_new), (0.5, 0.5))   # two decode-length buckets
        tr = poisson_trace(args.requests, 1e-5, sizes=sizes, seed=0) \
            if args.trace == "poisson" else \
            mmpp_trace(args.requests, 1e-5, 5e-5, dwell_base=2e6,
                       dwell_burst=5e5, sizes=sizes, seed=0)
        print(f"replaying a {tr.kind} trace: {len(tr)} requests, "
              f"{tr.total_samples} decode tokens")
        t0 = time.time()
        outs = sess.replay_trace(tr, vocab_size=cfg.vocab_size,
                                 prompt_len=args.prompt_len)
        dt = time.time() - t0
    else:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len)
                   for _ in range(args.requests)]
        t0 = time.time()
        outs = sess.generate(prompts, max_new=args.max_new)
        dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    dev = jax.devices()[0]
    print(f"arch={cfg.name} served {args.requests} requests "
          f"({n_tok} new tokens) in {dt:.2f}s -> {n_tok / dt:.1f} tok/s "
          f"(host clock, compiles included) on {dev.platform} "
          f"{dev.device_kind}")
    print(f"first completion: {outs[0][:10]}...")
    assert len(outs) == args.requests


if __name__ == "__main__":
    main()
