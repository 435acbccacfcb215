"""What the serving path tells a profiler: the model's named scopes in the
compiled executables (and that they change nothing else), the scheduler's
host spans, and the wall-clock stamps on each request."""
import contextlib
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.inference.scheduler import ContinuousBatcher, Request
from repro.inference.spec import ReplicaSpec, build_replica
from repro.launch import hlo_analysis as H
from repro.models.transformer import init_params, make_plan

SLOTS, S_MAX, BLOCK, CHUNK = 3, 96, 8, 16
KV_SCOPES = {"kv_carry", "kv_write", "kv_gather", "attention"}
MODEL_SCOPES = {"kv_carry", "kv_write", "kv_gather", "attention", "qkv",
                "o_proj", "mlp", "norm", "embed"}


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Compile here, never load: an entry of JAX's persistent cache (on
    if an earlier test in this process turned it on) is keyed without
    metadata, so it may hold the same program without the scopes."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke("llama3.2-1b")
    ap = make_plan(cfg, 1)
    return cfg, ap, init_params(jax.random.PRNGKey(0), ap)


def _batcher(tiny):
    _, ap, params = tiny
    return ContinuousBatcher(ap, params, slots=SLOTS, s_max=S_MAX,
                             block_size=BLOCK, admit_mode="chunked",
                             admit_chunk=CHUNK)


def _executables(b):
    """(name, jitted fn, args) of the decode step and both chunk steps."""
    b._push_state()
    tok = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    chunk = (b.params, b.cache, tok, tok, i32, i32, b._rng)
    return [("step", b._serve, (b.params, b.cache, b._state)),
            ("admit_mid", b._admit_chunked_mid, chunk),
            ("admit_final", b._admit_chunked, chunk)]


def test_op_scopes_names_the_innermost_scope():
    def f(x, w):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ w)
            with jax.named_scope("tp_all_reduce"):
                y = y * 2.0
        with jax.named_scope("not_a_scope"):
            return jnp.exp(y)

    x = jnp.ones((4, 8))
    text = jax.jit(f).lower(x, jnp.ones((8, 8))).compile().as_text()
    got = H.op_scopes(text)
    assert H.module_name(text) == "f"
    assert set(got.values()) <= {"mlp", "tp_all_reduce"}
    assert "mlp" in got.values()
    lines = {m.group(1): m.group(2) for m in re.finditer(
        r"%?([\w.\-]+) = [^\n]*? (dot|exponential)\(", text)}
    assert [got.get(n) for n, op in lines.items() if op == "dot"] == ["mlp"]
    assert all(n not in got for n, op in lines.items()
               if op == "exponential")


@pytest.mark.parametrize("which", ["step", "admit_mid", "admit_final"])
def test_scopes_cover_every_cache_op(tiny, which):
    """In the program as traced, every instruction that produces the K/V
    pool (one layer's or the stack) or its gathered logical view carries
    a cache or attention scope; every model scope is present."""
    b = _batcher(tiny)
    _, fn, args = dict((n, (n, f, a)) for n, f, a in _executables(b))[which]
    lowered = fn.lower(*args)
    k = b.cache["k"]                       # (L, n_blocks, block, U, hd)
    U, hd = k.shape[-2:]
    pool = {tuple(k.shape), tuple(k.shape[1:])}
    views = {(SLOTS, S_MAX, U, hd), (1, S_MAX, U, hd),
             (SLOTS, S_MAX // BLOCK, BLOCK, U, hd),
             (S_MAX // BLOCK, BLOCK, U, hd)}
    text = lowered.as_text(dialect="hlo", debug_info=True)
    scopes = H.op_scopes(text)
    seen = 0
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]"
                         r"\{?[^ ]* ([\w\-]+)\(", text, re.M):
        name, dims, op = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        if op in ("parameter", "get-tuple-element", "tuple") \
                or shape not in pool | views:
            continue
        seen += 1
        assert scopes.get(name) in KV_SCOPES, (name, op, shape)
    assert seen
    compiled = set(H.op_scopes(fn.lower(*args).compile().as_text()).values())
    want = MODEL_SCOPES | ({"head", "sample"} if which != "admit_mid"
                           else set())
    assert want <= compiled, want - compiled


@pytest.mark.parametrize("which", ["step", "admit_mid", "admit_final"])
def test_scopes_leave_the_compiled_program_unchanged(tiny, which,
                                                     monkeypatch):
    """Without its metadata, the executable compiles to the same program
    whether the scopes are there or not.  (A scope can shift the numbers
    JAX gives a few instructions, never what they do.)"""
    def text():
        b = _batcher(tiny)
        _, fn, args = [e for e in _executables(b) if e[0] == which][0]
        return H.program_text(fn.lower(*args).compile().as_text(),
                              renumber=True)

    scoped = text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert text() == scoped


def test_program_text_renumbers_and_drops_metadata():
    a = ('HloModule jit_f\n\nFileNames\n1 "a.py"\n\n'
         'ENTRY %main.3 {\n  %x.1 = f32[4]{0} parameter(0)\n'
         '  ROOT %tanh.7 = f32[4]{0} tanh(%x.1), '
         'metadata={op_name="jit(f)/mlp/tanh"}\n}')
    b = a.replace("tanh.7", "tanh.9").replace('"a.py"', '"b.py"') \
        .replace("mlp/", "")
    assert H.program_text(a) != H.program_text(b)
    assert H.program_text(a, renumber=True) \
        == H.program_text(b, renumber=True)
    assert "metadata" not in H.program_text(a) \
        and "a.py" not in H.program_text(a)
    assert H.program_text(a, renumber=True) != H.program_text(
        a.replace("tanh(", "exponential("), renumber=True)


def test_batcher_op_scopes_per_module(tiny):
    got = _batcher(tiny).op_scopes()
    assert set(got) == {"step", "admit_chunk"}
    assert len(got["step"]) == 1 and len(got["admit_chunk"]) == 2
    mid, final = got["admit_chunk"]
    assert "sample" not in mid.values() and "sample" in final.values()
    assert {"kv_carry", "kv_gather", "sample"} <= set(got["step"][0].values())


def test_op_scopes_past_a_cache_entry_without_scopes(tiny, tmp_path,
                                                    monkeypatch):
    """JAX's persistent cache keys an executable without its metadata, so
    an entry written by a build without the scopes loads in place of this
    one, and the batcher keeps what it loaded; op_scopes still reads this
    build's scopes."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    flags = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0,
             "jax_enable_compilation_cache": True}
    old = {f: getattr(jax.config, f) for f in flags}
    for f, v in flags.items():
        jax.config.update(f, v)
    cc.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            b = _batcher(tiny)
            b._push_state()
            b._serve.lower(b.params, b.cache, b._state).compile()
        b = _batcher(tiny)
        b._push_state()
        loaded = b._serve.lower(b.params, b.cache, b._state).compile()
        assert not H.op_scopes(loaded.as_text())   # the unscoped entry
        got = b.op_scopes()
        assert {"kv_carry", "kv_gather", "sample"} \
            <= set(got["step"][0].values())
    finally:
        for f, v in old.items():
            jax.config.update(f, v)
        cc.reset_cache()


def _host_spans(trace_dir) -> list:
    """(name, keywords) of each host event; a span's keywords come as
    event stats or, in older JAX, as a `#k=v#` suffix of its name."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    prof = ProfileData.from_file(str(path))
    out = []
    for plane in prof.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                name, _, kw = ev.name.partition("#")
                stats = dict(kv.split("=") for kv in kw.strip("#").split(",")
                             if "=" in kv)
                if ev.name.startswith("serve."):
                    stats.update({k: str(v) for k, v in ev.stats})
                out.append((name, stats))
    return out


def test_profiler_sees_the_serve_spans(tiny, tmp_path):
    cfg, ap, params = tiny
    spec = ReplicaSpec(arch="llama3.2-1b", slots=SLOTS, s_max=S_MAX,
                       block_size=BLOCK, admit_mode="chunked",
                       admit_chunk=CHUNK)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 20)
                    .astype(np.int32), max_new=3) for i in range(2)]
    build_replica(spec, ap=ap, params=params).run(list(reqs))   # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        b = build_replica(spec, ap=ap, params=params)
        b.run([Request(rid=r.rid, prompt=r.prompt, max_new=3)
               for r in reqs])
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    assert {"serve.build", "serve.tick", "serve.admit", "serve.admit.table",
            "serve.admit.chunks", "serve.admit.first_token", "serve.step",
            "serve.step.prepare", "serve.step.dispatch",
            "serve.step.readback", "serve.step.commit"} \
        <= {n for n, _ in spans}
    admits = sorted((kw["rid"], kw["chunks"]) for n, kw in spans
                    if n == "serve.admit")
    assert admits == [("0", "2"), ("1", "2")]    # 20 tokens, chunks of 16


def test_request_stamps_are_ordered(tiny):
    cfg, ap, params = tiny
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 5 + 9 * i)
                    .astype(np.int32), max_new=2 + i) for i in range(5)]
    b = _batcher(tiny)
    b.run(reqs)
    for r in reqs:
        assert 0 < r.admitted_s <= r.first_token_s <= r.done_s, r
    # stamps are perf_counter seconds, the clock a caller stamps arrivals on
    import time
    assert max(r.done_s for r in reqs) <= time.perf_counter()
