"""Smoke run of the serving path on TPU chips, through the entry points a
user calls: ``ReplicaSpec`` -> ``build_replica`` -> ``ContinuousBatcher``.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips, tensor-parallel only

One chip: codeqwen1.5-7b at its published widths (d_model 4096, 32 MHA
heads of 128, d_ff 13440, vocab 92416, untied head), cut to 16 of its 32
layers so that the weights (9.0 GB bf16) and a paged cache of 8 slots x
2048 positions (4 GiB) fit one 16 GB chip.  The served greedy tokens are
checked against a full-sequence forward of the same weights.

Four chips: all 32 layers at tp=4, served once with the flat all-reduce
and once with ``hier_rd`` over a logical (pod=2) x (model=2) split of the
four chips; each checked against the same full-sequence forward (run on
the sharded weights), and their greedy tokens compared.

Weights are random, from ``--seed``; so are the 8 requests (prompts of
256-1536 tokens, 64 new tokens each, greedy).  Any failed check ends the
run with a non-zero exit and no result line: no TPU found, a request not
completed, a token outside [0, vocab), a step whose logits were not all
finite, a served token that is not (near-)argmax of the reference.  The
times printed are of this one run: a smoke run, not a benchmark.

The last line of stdout is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "codeqwen1.5-7b"
ONE_CHIP_LAYERS = 16            # of 32: what fits one chip with its cache
N_REQUESTS, NEW_TOKENS = 8, 64
PROMPT_MIN, PROMPT_MAX = 256, 1536
SLOTS, S_MAX, BLOCK, CHUNK = 8, 2048, 16, 256
# a served token may trail the reference argmax logit by this much (logits
# of this init have unit scale; a wrong path trails by ~4)
REF_GAP_TOL = 0.25


def fail(msg: str):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def say(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def make_requests(seed: int, vocab: int):
    import numpy as np
    from repro.inference.scheduler import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new=NEW_TOKENS) for i, n in enumerate(lens)]


def replica_spec(seed: int, **kw):
    from repro.inference.spec import ReplicaSpec
    return ReplicaSpec(arch=ARCH, smoke=False, slots=SLOTS, s_max=S_MAX,
                       block_size=BLOCK, admit_mode="chunked",
                       admit_chunk=CHUNK, temperature=0.0, seed=seed, **kw)


def pallas_calls(fn, *args) -> int:
    """pallas_call equations in ``fn``'s traced program (nothing runs)."""
    import jax
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def serve(spec, ap, seed: int):
    """Build one replica, compile it on a short warm-up request, serve the
    requests; checks completion, token range and finite logits."""
    import numpy as np
    import jax
    from repro.inference.scheduler import Request
    from repro.inference.spec import build_replica
    vocab = ap.cfg.vocab_size
    t0 = time.perf_counter()
    batcher = build_replica(spec, ap=ap)
    jax.block_until_ready((batcher.params, batcher.cache))
    t_build = time.perf_counter() - t0
    # two chunks + two decode steps: compiles every executable the
    # requests use (mid chunk, final chunk, serve step)
    t0 = time.perf_counter()
    warm = Request(rid=10_000, prompt=np.ones(CHUNK + 1, np.int32), max_new=2)
    batcher.run([warm])
    t_warm = time.perf_counter() - t0
    if warm.output is None:
        fail("warm-up request did not complete")
    reqs = make_requests(seed, vocab)
    t0 = time.perf_counter()
    batcher.run(reqs)
    t_serve = time.perf_counter() - t0
    m = batcher.metrics(reqs)
    short = [r.rid for r in reqs
             if r.output is None or len(r.output) != NEW_TOKENS]
    if short:
        fail(f"requests not completed with {NEW_TOKENS} tokens: {short}")
    toks = np.stack([r.output for r in reqs])
    if toks.min() < 0 or toks.max() >= vocab:
        fail(f"token outside [0, {vocab}): min {toks.min()} max "
             f"{toks.max()}")
    if m.quarantines:
        fail(f"{m.quarantines} slot-steps had non-finite logits")
    if m.preemptions or m.shed_requests:
        fail(f"unexpected preemptions={m.preemptions} "
             f"shed={m.shed_requests}")
    say(f"  build (weight init + cache) {t_build:.2f} s, warm-up "
        f"(compile + 1 short request) {t_warm:.2f} s, serve "
        f"{t_serve:.2f} s  [smoke-run wall times, not a benchmark]")
    say(f"  requests completed {m.completed}/{len(reqs)}, tokens emitted "
        f"{int(toks.size)}, all in [0, {vocab}), finite-logit flags all "
        f"true ({m.steps} decode steps)")
    n_serve = pallas_calls(batcher._serve, batcher.params, batcher.cache,
                           batcher._state)
    n_chunk = pallas_calls(
        batcher._admit_chunked, batcher.params, batcher.cache,
        np.zeros((1, CHUNK), np.int32), np.zeros((1, CHUNK), np.int32),
        np.int32(0), np.int32(0), jax.random.PRNGKey(0))
    if n_serve or n_chunk:
        fail(f"unexpected Pallas kernels on the served path: decode "
             f"{n_serve}, admission {n_chunk}")
    say("  served path: XLA only, no Pallas kernel (0 pallas_call in the "
        "traced decode and admission steps)")
    return batcher, reqs, toks


def param_report(params):
    import jax
    total, where = 0, {}
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            where[str(sh.device)] = where.get(str(sh.device), 0) \
                + sh.data.nbytes
    return total, where


def reference_check(batcher, ap, req):
    """Teacher-forced full-sequence forward of the same weights over one
    request's prompt + served tokens: each served token must be (near-)
    argmax of the reference logits at its position."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core.pcontext import LOCAL
    from repro.models.transformer import forward_lm
    vocab = ap.cfg.vocab_size
    seq = np.concatenate([req.prompt, req.output[:-1]]).astype(np.int32)
    fwd = jax.jit(lambda p, t: forward_lm(p, t, ap, LOCAL)[0])
    logits = fwd(batcher.params, jnp.asarray(seq[None]))
    S = len(req.prompt)
    ref = np.asarray(logits[0, S - 1:, :vocab], np.float32)
    served = np.asarray(req.output)
    gap = ref.max(axis=-1) - ref[np.arange(len(served)), served]
    agree = float(np.mean(ref.argmax(axis=-1) == served))
    say(f"  reference (full forward, request {req.rid}, prompt {S}): "
        f"argmax agreement {agree:.4f}, max logit gap {gap.max():.4f} "
        f"(limit {REF_GAP_TOL})")
    if not np.isfinite(ref).all():
        fail("reference logits not finite")
    if gap.max() > REF_GAP_TOL:
        fail(f"served tokens trail the reference argmax by up to "
             f"{gap.max():.4f} > {REF_GAP_TOL}")


def one_chip(seed: int):
    import jax
    from repro.configs import get_config
    from repro.models.transformer import make_plan
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=ONE_CHIP_LAYERS)
    ap = make_plan(cfg, 1)
    say(f"one chip: {ARCH} at published widths (d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}), layers held {cfg.n_layers} of "
        f"{full.n_layers} (cut {full.n_layers - cfg.n_layers})")
    say(f"  {N_REQUESTS} requests, prompts {PROMPT_MIN}-{PROMPT_MAX}, "
        f"{NEW_TOKENS} new tokens, greedy; {SLOTS} slots, s_max {S_MAX}, "
        f"paged blocks of {BLOCK}, chunked admission at {CHUNK}")
    batcher, reqs, _ = serve(replica_spec(seed), ap, seed)
    total, where = param_report(batcher.params)
    say(f"  parameter bytes {total} on {sorted(where)}")
    reference_check(batcher, ap, min(reqs, key=lambda r: len(r.prompt)))
    dev = jax.devices()[0]
    st = dev.memory_stats()
    say(f"  {dev} peak_bytes_in_use {st['peak_bytes_in_use']} of "
        f"bytes_limit {st['bytes_limit']}")


def four_chips(seed: int):
    import gc
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.models.transformer import make_plan
    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = get_config(ARCH)
    ap = make_plan(cfg, 4)
    say(f"four chips: {ARCH}, all {cfg.n_layers} layers at published "
        f"widths, tp=4; flat vs hier_rd (pods=2 x 2)")
    tokens = {}
    for name, kw in (("flat", dict(tp=4, pods=1, ar_strategy="flat")),
                     ("hier_rd", dict(tp=4, pods=2, ar_strategy="hier_rd"))):
        say(f"{name}:")
        batcher, reqs, toks = serve(replica_spec(seed, **kw), ap, seed)
        total, where = param_report(batcher.params)
        say(f"  parameter bytes {total}, per device {where}")
        biggest = max(where.values())
        if len(where) != 4 or biggest > 0.3 * total:
            fail(f"weights not split over four chips: {where}")
        # the plain forward on the sharded weights, partitioned by the
        # compiler: an independent check of each strategy's served tokens
        reference_check(batcher, ap, min(reqs, key=lambda r: len(r.prompt)))
        for d in jax.devices()[:4]:
            st = d.memory_stats()
            say(f"  {d} bytes_in_use {st['bytes_in_use']} "
                f"peak_bytes_in_use {st['peak_bytes_in_use']}")
        tokens[name] = toks
        del batcher
        gc.collect()
    a, b = tokens["flat"], tokens["hier_rd"]
    same = a == b
    first = [int(np.argmin(row)) if not row.all() else None for row in same]
    say(f"flat vs hier_rd greedy tokens: {int(same.sum())}/{same.size} "
        f"agree; first diverging position per request: {first}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve all 32 layers at tp=4, flat vs hier_rd, "
                         "and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro package next to {pathlib.Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found platform {dev.platform!r} ({dev.device_kind}), "
             "not a TPU")
    from repro.launch.compile_cache import enable_compile_cache
    say(f"devices: {len(jax.devices())} x {dev.device_kind}; compile "
        f"cache {enable_compile_cache()}")
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
