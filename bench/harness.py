"""One run of one cell: set-up, a measured window, the comparison that
decides `correct`, and the numbers of the result line.

Everything specific to a cell is found by name: the cell in
`BENCHMARK.json`, its configuration in the file that names, its traffic in
`traffic/<mix>.json`, each metric's reader in `metrics/<metric>.py`, the
chip's peaks in `peaks.json`.  Nothing here tests for a cell's name.

The system under test is the program's serving path as a user builds it:
`ReplicaSpec` -> `build_replica` -> `ContinuousBatcher`, driven one
`tick` at a time from this file's own wall-clock queue.  A token's time is
the host clock when the `tick` that produced it returns: when a server
built on this scheduler could hand it to its user.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 8.0   # a traced run traces the first this-many seconds
DRAIN_S = 60.0        # open loop: most seconds past the close for the
                      # window's requests to get their first token
SAMPLE_TOKENS = 384   # served tokens the correctness sample reaches,
SAMPLE_MIN = 4        # ... with at least this many requests
SAMPLE_MAX = 12       # ... and at most this many
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

clock = time.perf_counter


class Failed(Exception):
    """A run that ends without a result line."""


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    bench: dict

    @property
    def serve(self) -> dict:
        return self.config["serve"]


def find_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    wls = {w["name"]: w for w in bench["workloads"]}
    if name not in wls:
        raise Failed(f"no workload {name!r} in BENCHMARK.json")
    wl = wls[name]
    cfg_file = {c["name"]: c for c in bench["configs"]}[wl["config"]]["file"]
    return Cell(name, wl, load_json(ROOT / cfg_file),
                load_json(BENCH / "traffic" / f"{wl['traffic']}.json"), bench)


def metrics_of(cell: Cell, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer."""
    e2e = [m for m in cell.bench["end_to_end"]
           if cell.name in m.get("workloads", [cell.name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in cell.bench["per_layer"]
            if (cell.name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric: str) -> Callable:
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


_COMPILES = [0]     # backend compilations seen by this process
_LISTENING = []


def _count_compiles():
    import jax
    if _LISTENING:
        return

    def on_event(name, secs, **kw):
        if name == COMPILE_EVENT:
            _COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _LISTENING.append(on_event)


def devices_for(cell: Cell, require_chip: bool):
    """The chips the cell runs on; no accelerator or too few is an error."""
    import jax
    devs = jax.devices()
    need = cell.workload["chips"]
    if require_chip and devs[0].platform != "tpu":
        raise Failed(f"JAX found no TPU (platform {devs[0].platform!r}, "
                     f"{devs[0].device_kind}); this benchmark measures "
                     "only on the chip")
    if len(devs) < need:
        raise Failed(f"the cell needs {need} chips, JAX found {len(devs)}")
    return devs[:need]


def model_config(m: dict):
    """The program's model config for a configuration file's sizes."""
    from repro.configs import get_config
    import weights as W
    return dataclasses.replace(
        get_config(m["arch"]), n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        qkv_bias=m["qkv_bias"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], tie_embeddings=m["tie_word_embeddings"],
        sliding_window=0, dtype=W.DTYPES[m["dtype"]])


def build(cell: Cell, seed: int, devs):
    """The replica, with weights made on the device from ``seed``."""
    from repro.inference.spec import ReplicaSpec, build_replica
    from repro.models.transformer import make_plan
    from repro.parallel.steps import serve_param_shardings
    from repro.parallel.topology import mesh_and_ctx
    import weights as W
    m, sv = cell.config, cell.serve
    tp = sv["tp"]
    ap = make_plan(model_config(m), tp)
    spec = ReplicaSpec(arch=m["arch"], smoke=False, tp=tp, pods=sv["pods"],
                       slots=sv["slots"], s_max=sv["s_max"],
                       block_size=sv["block_size"], admit_mode="chunked",
                       admit_chunk=sv["admit_chunk"],
                       temperature=sv["temperature"], seed=0)
    group = list(devs[:tp])
    mesh, ctx, _ = mesh_and_ctx(tp, sv["pods"], ar_strategy=spec.ar_strategy,
                                devices=group if tp > 1 else None)
    shardings = serve_param_shardings(ap, ctx, mesh, device=group[0])
    params = W.program_params(seed, m, ap.gqa.q_map, ap.gqa.kv_map,
                              ap.vocab_pad, out_shardings=shardings)
    return build_replica(spec, ap=ap, params=params, devices=group)


def warm_up(batcher, prompt_len: int, max_new: int = 3):
    """Compiles every executable the cell's traffic uses: the admission
    chunks of the longest prompt (mid and final) and the decode step,
    twice: on state pushed from the host and on the state a step hands
    back, whose shardings differ across chips."""
    from repro.inference.scheduler import Request
    warm = Request(rid=2 ** 30, prompt=np.ones(prompt_len, np.int32),
                   max_new=max_new)
    batcher.run([warm])
    if warm.output is None:
        raise Failed("warm-up request did not complete")


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """What the traced part of the window did, counted by the harness."""
    on: bool = False
    steps: List[np.ndarray] = dataclasses.field(default_factory=list)
    prompts: List[int] = dataclasses.field(default_factory=list)


def instrument(batcher, rec: Record):
    """Spans around the batcher's admission and decode step, and a record
    of the contexts each step attends (traced runs only)."""
    from jax.profiler import TraceAnnotation
    step, admit = batcher.step, batcher._admit

    def step_w(now):
        if rec.on and batcher.active_mask.any():
            rec.steps.append(batcher.positions[batcher.active_mask] + 1)
        with TraceAnnotation("bench.step"):
            return step(now)

    def admit_w(slot, req, now):
        with TraceAnnotation("bench.admit"):
            ok = admit(slot, req, now)
        if ok and rec.on:
            rec.prompts.append(len(req.prompt))
        return ok

    batcher.step, batcher._admit = step_w, admit_w


class Window:
    """Drives the batcher from the mix's requests for ``seconds``."""

    def __init__(self, batcher, stream, mix: dict, slots: int,
                 seconds: float):
        from repro.inference.scheduler import Request
        self.Request = Request
        self.b, self.stream, self.mix = batcher, stream, mix
        self.slots, self.T = slots, float(seconds)
        self.queue: list = []
        self.live: Dict[int, object] = {}
        self.reqs: Dict[int, object] = {}
        self.stamps: Dict[int, List[float]] = {}
        self.next_rid = 0
        self.ticks = 0
        self.t0 = clock()
        self.late: List[float] = []       # generator lateness, seconds
        self.queue_len: List[tuple] = []  # (time, waiting) per tick

    def _new(self, rid: int):
        it = self.stream.get(rid)
        r = self.Request(rid=it.rid, prompt=it.prompt, max_new=it.max_new)
        self.reqs[rid] = r
        return r

    def refill(self, now: float):
        if self.stream.loop == "backlog":
            want = self.mix["waiting_per_slot"] * self.slots \
                + self.slots - len(self.live)
            while len(self.queue) < want:
                self.queue.append(self._new(self.next_rid))
                self.next_rid += 1
        else:
            arr = self.stream.arrivals
            while self.next_rid < len(arr) and arr[self.next_rid] <= now:
                self.queue.append(self._new(self.next_rid))
                self.next_rid += 1

    def tick(self):
        from jax.profiler import TraceAnnotation
        pending = list(self.queue)
        with TraceAnnotation("bench.tick"):
            self.b.tick(self.queue, self.ticks)
        t = clock()
        self.ticks += 1
        for r in pending[:len(pending) - len(self.queue)]:
            self.live[r.rid] = r
            self.stamps[r.rid] = []
        for rid, r in list(self.live.items()):
            n = len(self.b.outputs.get(rid, ()))
            st = self.stamps[rid]
            if n > len(st):
                st.extend([t] * (n - len(st)))
            if r.output is not None:
                del self.live[rid]
        self.queue_len.append((t - self.t0, len(self.queue)))

    def fill(self):
        """Backlog: the first tick fills every slot (set-up)."""
        if self.stream.loop == "backlog":
            self.refill(0.0)
            self.tick()
            if len(self.live) < self.slots:
                raise Failed(f"backlog filled {len(self.live)} of "
                             f"{self.slots} slots")

    def _window_done(self) -> bool:
        return all(self.stamps.get(rid) for rid in range(self.stream.n_window))

    def run(self, trace_dir: Optional[str], rec: Record,
            trace_s: float = TRACE_SECONDS) -> float:
        """The window; returns the traced seconds (host clock), or 0.
        A traced run traces the window's first ``trace_s`` seconds."""
        import jax
        from jax.profiler import TraceAnnotation
        trace_s = min(trace_s, self.T) if trace_dir else 0.0
        span = None
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            span = TraceAnnotation("bench.window")
            span.__enter__()
            rec.on = True
        traced = 0.0
        self.t0 = clock()
        open_loop = self.stream.loop == "open"
        while True:
            now = clock() - self.t0
            if span is not None and now >= trace_s:
                span.__exit__(None, None, None)
                traced, span, rec.on = now, None, False
                jax.profiler.stop_trace()
            if now >= self.T and (not open_loop or self._window_done()
                                  or now >= self.T + DRAIN_S):
                break
            self.refill(now)
            if not self.queue and not self.live:
                arr = self.stream.arrivals
                if arr is None or self.next_rid >= len(arr):
                    break
                due = arr[self.next_rid]
                wake = due
                if span is not None:
                    wake = min(wake, trace_s)
                if now < self.T:
                    wake = min(wake, self.T)
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, wake - now))
                if wake == due:
                    self.late.append(max(0.0, clock() - self.t0 - due))
                continue
            self.tick()
        if span is not None:
            span.__exit__(None, None, None)
            traced = clock() - self.t0
            jax.profiler.stop_trace()
        return traced

    def times(self) -> Dict[int, np.ndarray]:
        """Token times of every admitted request, seconds after the open."""
        return {rid: np.asarray(st) - self.t0
                for rid, st in self.stamps.items()}


# ---------------------------------------------------------------------------
# what the readers see
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """Everything a metric's reader may read about one run."""
    cell: Cell
    seconds: float
    setup_s: float
    token_times: Dict[int, np.ndarray]   # rid -> token times, window clock
    arrivals: Dict[int, float]           # open loop: window rid -> due time
    chips: int
    peaks: dict
    trace: object = None                 # trace_reduce.Trace of the window
    traced_s: float = 0.0
    record: Optional[Record] = None

    def tokens_in_window(self) -> int:
        return sum(int(((t >= 0) & (t < self.seconds)).sum())
                   for t in self.token_times.values())

    def gaps_in_window(self) -> np.ndarray:
        """Gaps between consecutive tokens of a request, after its first
        two (which one tick delivers together), both ends in the window."""
        out = []
        for t in self.token_times.values():
            g = np.diff(t[1:])
            ok = (t[1:-1] >= 0) & (t[2:] < self.seconds)
            out.append(g[ok])
        return np.concatenate(out) if out else np.zeros(0)

    def ttfts(self) -> np.ndarray:
        """Time to first token of each request due in the window, from its
        due time; inf where none came."""
        return np.asarray([
            self.token_times[r][0] - a
            if r in self.token_times and len(self.token_times[r])
            else np.inf for r, a in self.arrivals.items()])


def peaks_of(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise Failed(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample(reqs, seed: int) -> list:
    """Finished requests for the check, drawn from the seed: the longest,
    then others until SAMPLE_TOKENS served tokens and SAMPLE_MIN requests,
    or SAMPLE_MAX requests."""
    done = [r for r in reqs if r.output is not None
            and len(r.output) == r.max_new]
    if not done:
        raise Failed("no request finished; nothing to check")
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.output), r.rid))
    rest = [r for r in done if r is not longest]
    np.random.default_rng([seed, 11]).shuffle(rest)
    out, served = [longest], len(longest.output)
    for r in rest:
        if len(out) >= SAMPLE_MAX or (served >= SAMPLE_TOKENS
                                      and len(out) >= SAMPLE_MIN):
            break
        out.append(r)
        served += len(r.output)
    return out


def check(cell: Cell, seed: int, reqs, devs, control: bool) -> dict:
    """Readings of the served tokens against the float32 reference."""
    import reference
    vocab = cell.config["vocab_size"]
    picked = sample(reqs, seed)
    seqs = [(np.concatenate([r.prompt, r.output[:-1]]), len(r.prompt),
             r.output) for r in picked]
    bad_tok = sum(int(((r.output < 0) | (r.output >= vocab)).sum())
                  for r in reqs if r.output is not None)
    got = reference.readings(seed, cell.config, seqs, control=control,
                             devices=devs)
    gaps = np.concatenate([g["gap"] for g in got])
    out = {"logit_gap_max": float(gaps.max()),
           "logit_gap_mean": float(gaps.mean()),
           "served_tokens_checked": int(gaps.size),
           "requests_checked": len(picked),
           "argmax_agreement": float(np.mean(gaps == 0)),
           "tokens_out_of_range": bad_tok}
    if control:
        cg = np.concatenate([g["control_gap"] for g in got])
        out["control_gap_max"] = float(cg.max())
        out["control_gap_mean"] = float(cg.mean())
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def say(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             compile_cache: bool = True, control: bool = False,
             do_check: bool = True, after_build=None,
             trace_seconds: float = TRACE_SECONDS,
             keep_trace: Optional[str] = None) -> dict:
    """One run; returns the result (see ``result_line``).  ``after_build``
    (tests only) may alter the built batcher before the window;
    ``keep_trace`` copies the traced run's profile there."""
    import jax
    import generator
    import trace_reduce
    devs = devices_for(cell, require_chip)
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        say(f"compile cache {enable_compile_cache()}")
    _count_compiles()
    peaks = peaks_of(devs[0].device_kind) if require_chip else {}
    sv, m = cell.serve, cell.config
    stream = generator.Stream(cell.mix, seed, m["vocab_size"], seconds)
    batcher = build(cell, seed, devs)
    jax.block_until_ready((batcher.params, batcher.cache))
    if after_build is not None:
        after_build(batcher)
    warm_up(batcher, int(cell.mix["prompt_tokens"]["max"]))
    rec = Record()
    if trace:
        instrument(batcher, rec)
    batcher.reset_run_stats()
    win = Window(batcher, stream, cell.mix, sv["slots"], seconds)
    win.fill()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles0 = _COMPILES[0]
    t_open = clock()
    setup_s = t_open - t_start
    traced = win.run(trace_dir, rec, trace_seconds)
    compiles = _COMPILES[0] - compiles0
    counters = batcher.metrics([])
    say(f"window {seconds:g} s: {win.ticks} ticks, {batcher.steps_run} "
        f"decode steps, {len(win.stamps)} requests admitted, backend "
        f"compilations inside the window: {compiles}")
    if win.late:
        say(f"generator lateness: max {max(win.late) * 1e3:.3f} ms, mean "
            f"{np.mean(win.late) * 1e3:.3f} ms over {len(win.late)} waits")
    used = [d.memory_stats() or {} for d in devs]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in used)
    arrivals = {}
    if stream.loop == "open":
        arrivals = {r: float(stream.arrivals[r])
                    for r in range(stream.n_window)}
    reqs = list(win.reqs.values())
    queue_len = win.queue_len
    run = Run(cell=cell, seconds=float(seconds), setup_s=setup_s,
              token_times=win.times(), arrivals=arrivals, chips=len(devs),
              peaks=peaks, traced_s=traced, record=rec)
    # the program's state goes before the reference runs
    del batcher, win
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    tr = None
    if trace:
        paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if not paths:
            raise Failed("the profiler wrote no trace")
        if keep_trace:
            shutil.copy(paths[-1], keep_trace)
        tr = trace_reduce.load(str(paths[-1]), chips=len(devs))
        run.trace = tr
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for mt in metrics_of(cell, trace):
        v = reader(mt["name"])(run)
        if v is None:
            say(f"metric {mt['name']}: nothing to read, left out")
            continue
        metrics[mt["name"]] = {"value": float(v), "unit": mt["unit"]}
    attempted = len(arrivals) if arrivals else sum(
        1 for t in run.token_times.values()
        if ((t >= 0) & (t < seconds)).any())
    failed = int(np.isinf(run.ttfts()).sum()) if arrivals else 0
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device, "compiles_in_window": compiles,
              "preemptions": counters.preemptions,
              "queue": queue_summary(queue_len, seconds)}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.longest_gaps(10)}
    checks = {}
    if do_check:
        t_ref = clock()
        got = check(cell, seed, reqs, devs, control)
        say(f"reference over {got['requests_checked']} requests, "
            f"{got['served_tokens_checked']} served tokens, in "
            f"{clock() - t_ref:.1f} s; argmax agreement "
            f"{got['argmax_agreement']:.4f}")
        result["readings"] = got
        checks = checks_of(cell, got, counters.quarantines)
    result["correct"] = verdict(checks)
    result["checks"] = checks
    return result


def checks_of(cell: Cell, got: dict, quarantines: int) -> dict:
    """Each number compared, beside its limit: the readings that the
    configuration's ``correct`` names, and tokens out of the vocabulary
    and quarantined steps, which have to be none."""
    checks = {k: {"value": got[k], "limit": lim}
              for k, lim in cell.config["correct"].items()}
    checks["tokens_out_of_range"] = {"value": got["tokens_out_of_range"],
                                     "limit": 0}
    checks["quarantined_steps"] = {"value": quarantines, "limit": 0}
    return checks


def verdict(checks: dict) -> bool:
    """`correct`: every number at or under its limit (none unset)."""
    return bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


def queue_summary(queue_len, seconds: float) -> dict:
    """Requests waiting for a slot: mean over each half of the window and
    at its close (a queue that grows from half to half is over capacity)."""
    t = np.asarray([q[0] for q in queue_len])
    n = np.asarray([q[1] for q in queue_len], float)
    half = lambda lo, hi: float(n[(t >= lo) & (t < hi)].mean()) \
        if ((t >= lo) & (t < hi)).any() else 0.0
    at_close = n[t < seconds]
    return {"first_half": half(0, seconds / 2),
            "second_half": half(seconds / 2, seconds),
            "at_close": float(at_close[-1]) if at_close.size else 0.0}


def result_line(res: dict) -> str:
    """The last line of standard output; ``checks`` comes last."""
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    out = {k: res[k] for k in keys}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    for k in ("compiles_in_window", "preemptions", "queue", "readings"):
        if k in res:
            out[k] = res[k]
    out["checks"] = res["checks"]
    return json.dumps(out)


def check_lines(res: dict) -> List[str]:
    return [f"check {k}: {c['value']} (limit {c['limit']})"
            for k, c in res["checks"].items()]
