"""End-to-end serving driver (the paper's kind of workload): serve a ~100M
llama-style model with batched requests through the continuous-batching
scheduler, reporting TTFT and throughput.

    PYTHONPATH=src python examples/serve_batched.py [--small]

(--small switches to a smoke model so the demo finishes in seconds on CPU.)
"""
import argparse

import jax

from repro.models.common import ModelConfig
from repro.models.transformer import make_plan, init_params
from repro.inference.scheduler import make_trace
from repro.inference.spec import ReplicaSpec, build_replica

M100 = ModelConfig(  # ~100M params
    name="llama-100m", family="dense", n_layers=8, d_model=512,
    n_heads=8, n_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32000,
    rope_theta=1e4)

SMALL = ModelConfig(
    name="llama-2m", family="dense", n_layers=4, d_model=128,
    n_heads=4, n_kv_heads=2, head_dim=32, d_ff=512, vocab_size=4096)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--small", action="store_true")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--slots", type=int, default=8)
    args = p.parse_args()
    cfg = SMALL if args.small else M100
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.0f}M params)")
    ap = make_plan(cfg, 1)
    params = init_params(jax.random.PRNGKey(0), ap)
    # paged KV cache (16-token blocks) + recompile-free chunked admission
    # (arch is nominal: ap/params for the demo model are passed explicitly)
    sched = build_replica(
        ReplicaSpec(arch="llama3.2-1b", slots=args.slots, s_max=192,
                    block_size=16, admit_mode="chunked"),
        ap=ap, params=params)
    reqs = make_trace(args.requests, mean_in=24, mean_out=16, rate=4.0,
                      vocab=cfg.vocab_size, seed=0)
    done = sched.run(reqs)
    assert all(r.output is not None for r in done)
    m = sched.metrics(done)
    print(f"{m.completed} requests, {m.total_new_tokens} tokens in "
          f"{m.wall_s:.1f}s ({m.throughput_tok_s:.1f} tok/s)")
    print(f"TTFT p50 {m.ttft_steps_p50:.1f} steps, TPOT p50 "
          f"{m.tpot_steps_p50:.2f} steps; KV peak "
          f"{m.peak_kv_tokens} of {args.slots * 192} dense tokens")


if __name__ == "__main__":
    main()
