"""One run of one benchmark cell, end to end.

A cell of ``BENCHMARK.json`` names a configuration (its ``file``), a traffic
mix (``bench/traffic/<traffic>.json``) and may have a file of its own
(``bench/cells/<cell>.json``: the fixed arrival rate, the limits of its
correctness checks).  Every metric is read by ``bench/metrics/<name>.py``.
Nothing here names a cell, a configuration, a mix or a metric.

A run, in one process:

  1. set-up: build the model config from the configuration file, make the
     weights from the seed on the device (``weights.py``), build the paged
     engine as ``python -m repro.launch.serve --paged --async`` builds it,
     and warm up its two programs: batched prefill calls completing 1 ..
     ``prefill_batch`` prompts, and the decode step.  A closed-loop mix
     then starts its clients, staggered, so the window opens in a steady
     state.  ``setup_s`` ends where the window starts;
  2. the window: ``seconds`` of traffic through ``ServeEngine.submit`` and
     ``ServeEngine.step``.  An open-loop mix submits each request when it
     is due; a closed-loop mix submits a client's next request when its
     last token has come.  Every step, submit and wait of the loop runs
     inside a ``jax.profiler.TraceAnnotation``.  With ``trace`` on, the
     engine's telemetry records its spans and a profiler trace covers
     the last ``trace_s`` seconds of the window (host annotations, no
     Python tracer);
  3. after the window: the engine keeps stepping, with no new requests,
     until every request sent has its first token (at most ``drain_s``
     seconds); then the pipeline is drained, the peak device memory read
     and the engine freed;
  4. correctness: a sample of the finished requests, drawn from the seed
     and holding the longest, is run through the plain reference
     (``bench/references/<reference>.py``, f32); the widest gap by which a
     served token's reference logit lies below the reference's best is
     held against the cell's limit;
  5. the metrics: with ``trace`` off the cell's end-to-end metrics, with
     it on its per-layer metrics, each from its reader.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from traffic import Traffic, mean_len

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"
ANNOT = "bench."


class BenchError(Exception):
    """A run that cannot give a result (no chip, unknown device, a cell
    that does not exist)."""


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


# --------------------------------------------------------------- cells --

def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything a cell's run reads, found by the names in BENCHMARK.json."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = configs[cell["config"]]
    extra_path = root / "bench" / "cells" / f"{name}.json"
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]
    return dict(
        name=name, cell=cell, chips=int(cell["chips"]),
        conf=_json(root / config["file"]),
        mix=_json(root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        extra=_json(extra_path) if extra_path.exists() else {},
        end_to_end=e2e, per_layer=per_layer,
    )


def _reports(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench: Path = BENCH):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = bench / "metrics" / f"{metric}.py"
    for d in (bench, bench / "metrics"):
        if str(d) not in sys.path:
            sys.path.insert(0, str(d))
    if not path.exists():
        raise BenchError(f"no reader for metric {metric!r} ({path})")
    return _load(path, "bench_metric_" + re.sub(r"\W", "_", metric)).read


def load_reference(name: str, bench: Path = BENCH):
    """The plain reference ``bench/references/<name>.py``."""
    return _load(bench / "references" / f"{name}.py", f"bench_ref_{name}")


def model_config(conf: dict, name: str):
    """The program's ModelConfig for a configuration file (HF keys)."""
    from repro.configs.base import ModelConfig

    h = conf["num_attention_heads"]
    return ModelConfig(
        arch_id=name, family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=h, n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // h,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=bool(conf["qk_norm"]), qkv_bias=bool(conf["qkv_bias"]),
        rope_theta=float(conf["rope_theta"]), norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        param_dtype="bfloat16",
    )


def serve_args(mix: dict):
    """``launch/serve.py``'s arguments for the mix's engine settings."""
    from repro.launch import serve

    e = mix["engine"]
    gen = mix["output_len"]["max"]
    pages = e["pool_rows"] * math.ceil(e["max_seq_len"] / e["page_size"]) + 1
    argv = [
        "--arch", "bench", "--paged", "--async",
        "--batch", str(e["max_batch"]),
        "--prompt-len", str(e["max_seq_len"] - gen), "--gen", str(gen),
        "--page-size", str(e["page_size"]), "--num-pages", str(pages),
        "--prefill-chunk", str(e["prefill_chunk"]),
        "--prefill-batch", str(e["prefill_batch"]),
        "--kv-dtype", e["kv_dtype"], "--scheduler", e["scheduler"],
        "--prefix-cache" if e["prefix_cache"] else "--no-prefix-cache",
    ]
    if e.get("pipeline_depth", 1) != 1:
        raise BenchError("the served path runs pipeline_depth 1 (--async)")
    return serve.build_parser().parse_args(argv)


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if require_tpu and plat != "tpu":
        raise BenchError(f"no TPU: JAX runs on {plat!r}; nothing measured")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def percentile(values, q: float):
    """Nearest-rank ``q``-th percentile (a missing value is +inf)."""
    v = sorted(values)
    if not v:
        return None
    return v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)]


# ----------------------------------------------------------------- run --

@dataclasses.dataclass
class Rec:
    """One request as the client sees it (times on ``time.perf_counter``)."""
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float | None = None         # open loop: when it was due
    submit: float | None = None
    admit: float | None = None       # trace runs: the engine's admit instant
    first: float | None = None
    done: float | None = None
    req: object = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)


class Run:
    """State and results of one run; the metric readers read it."""

    def __init__(self, name, seed, seconds, trace, *, t_process=None,
                 root: Path = ROOT, require_tpu=True, bundle_hook=None,
                 rate=None, log=None):
        self.name, self.seed, self.seconds, self.trace = name, int(seed), float(seconds), bool(trace)
        self.t_process = time.perf_counter() if t_process is None else t_process
        c = load_cell(name, root)
        self.cell, self.conf, self.mix, self.extra = c["cell"], c["conf"], c["mix"], c["extra"]
        self.end_to_end, self.per_layer, self.chips = c["end_to_end"], c["per_layer"], c["chips"]
        self.require_tpu, self.bundle_hook = require_tpu, bundle_hook
        self.open = self.mix["loop"] == "open"
        self.rate = rate if rate is not None else self.extra.get("rate_per_s")
        self.log = log or (lambda *a: None)
        self.recs: list = []
        self.steps: list = []            # (t0, t1, [(prompt_len, start, end)])
        self.decode_tokens: list = []    # (t, kv_len, step number)
        self.k = 0                       # step() calls
        self.compiles_in_window = 0
        self.trace_host = None           # (t0, t1) of the profiler session
        self.trace_red = None
        self.tel_events, self.tel_offset = [], 0.0
        self.peaks = None
        self.memory_peak = None

    # ------------------------------------------------------------ set-up --

    def setup(self):
        import jax

        from repro.launch.serve import make_paged_engine
        from repro.models.model_zoo import build
        from repro.runtime import Telemetry
        from weights import make_params

        self.devices = devices_for(self.chips, self.require_tpu)
        dev = self.devices[0]
        if self.require_tpu:
            peaks = _json(BENCH / "peaks.json")["devices"]
            if dev.device_kind not in peaks:
                raise BenchError(f"no peaks for device kind {dev.device_kind!r}")
            self.peaks = peaks[dev.device_kind]
        self.cfg = model_config(self.conf, self.cell["config"])
        bundle = build(self.cfg)
        if self.bundle_hook is not None:
            bundle = self.bundle_hook(bundle)
        shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        self.params = make_params(shapes, self.seed, dev if self.require_tpu else None)
        self.tel = Telemetry(tracing=True, metrics=False) if self.trace else None
        kw = {"on_token": self._on_token}
        if self.tel is not None:
            kw["telemetry"] = self.tel
            self.tel_offset = time.perf_counter() - self.tel.clock()
        self.eng, *_ = make_paged_engine(serve_args(self.mix), bundle, self.params, **kw)
        self.by_rid = {}
        self.prefilling = []
        self.done_q = []
        e = self.mix["engine"]
        self.traffic = Traffic(self.mix, self.seed, self.cfg.vocab_size,
                               self.rate, self.seconds)
        # warm-up: a prefill call completing k prompts for each k (the
        # engine's eager scatter of first tokens has one shape per k),
        # then decode steps; the programs come from the compile cache
        # (k = 1 runs again last: the first call met the engine's token
        # buffer uncommitted to a device, and the scatter compiles anew
        # for a committed one)
        for k in [*range(1, e["prefill_batch"] + 1), 1]:
            for j in range(k):
                toks, gen = self.traffic.warmup(k * 100 + j, e["prefill_chunk"], 3)
                self.eng.submit([int(t) for t in toks], gen)
            self.eng.run_to_completion()
        if not self.open:
            self._start_clients()

    def _start_clients(self):
        """Each client's first request, a ``stagger`` of a mean request
        apart, so the window opens with the clients out of phase."""
        e, mix = self.mix["engine"], self.mix
        mean_steps = mean_len(mix["output_len"]) + math.ceil(
            mean_len(mix["prompt_len"]) / e["prefill_chunk"]
        )
        gap = max(int(mix["stagger"] * mean_steps), 1)
        self.next_idx = 0
        for _ in range(mix["clients"]):
            self._submit_next(time.perf_counter())
            for _ in range(gap):
                self._resubmit_done()
                self._step()

    # --------------------------------------------------------- the loop --

    def _on_token(self, r, idx, tok):
        rec = self.by_rid.get(r.req_id)
        if rec is None:
            return                      # a warm-up request
        t = time.perf_counter()
        rec.times.append(t)
        rec.tokens.append(int(tok))
        if idx == 0:
            rec.first = t
        else:
            self.decode_tokens.append((t, len(rec.prompt) + idx, self.k))
        if idx + 1 == rec.max_new:
            rec.done = t
            if not self.open:
                self.done_q.append(rec)

    def _submit(self, rec: Rec):
        import jax

        with jax.profiler.TraceAnnotation(ANNOT + "submit"):
            rec.submit = time.perf_counter()
            self.recs.append(rec)
            try:
                rec.req = self.eng.submit([int(t) for t in rec.prompt], rec.max_new)
            except ValueError as err:
                self.log(f"request {rec.idx} refused: {err}")
                return
            self.by_rid[rec.req.req_id] = rec
            self.prefilling.append(rec)

    def _submit_next(self, now):
        toks, gen, _ = self.traffic.request(self.next_idx)
        self._submit(Rec(self.next_idx, toks, gen, due=now))
        self.next_idx += 1

    def _resubmit_done(self):
        """Closed loop: each client whose reply is complete sends its next
        request."""
        while self.done_q:
            self.done_q.pop(0)
            self._submit_next(time.perf_counter())

    def _step(self):
        import jax

        before = [(rec, rec.req.prefill_pos) for rec in self.prefilling]
        self.k += 1
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANNOT + "step"):
            self.eng.step()
        t1 = time.perf_counter()
        chunks = [
            (len(rec.prompt), p0, rec.req.prefill_pos)
            for rec, p0 in before if rec.req.prefill_pos > p0
        ]
        self.steps.append((t0, t1, chunks))
        self.prefilling = [
            rec for rec in self.prefilling
            if rec.req.prefill_pos < len(rec.prompt)
        ]

    def window(self):
        import jax

        events = []
        listener = lambda name, *a, **kw: events.append(name)
        jax.monitoring.register_event_duration_secs_listener(listener)
        if self.open:
            plan, i = [], 0
            while True:
                toks, gen, due = self.traffic.request(i)
                if due >= self.seconds:
                    break
                plan.append(Rec(i, toks, gen, due=due))
                i += 1
        t0 = time.perf_counter()
        self.t0, self.t1 = t0, t0 + self.seconds
        self.setup_s = t0 - self.t_process
        # the profiler traces the window's last trace_s seconds, so that
        # writing the trace out delays nothing inside the window
        tr0 = self.t1 - min(self.mix["trace_s"], self.seconds)
        tdir = OUT / "trace" / self.name
        tracing = False
        jax.config.update("jax_log_compiles", True)
        if self.open:
            for rec in plan:
                rec.due += t0
        nxt = 0
        while True:
            now = time.perf_counter()
            if self.trace and not tracing and now >= tr0:
                shutil.rmtree(tdir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0      # host: annotations only
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(str(tdir), profiler_options=opts)
                tracing, t_tr = True, time.perf_counter()
            if now >= self.t1:
                break
            if self.open:
                while nxt < len(plan) and plan[nxt].due <= now:
                    self._submit(plan[nxt])
                    nxt += 1
                if self.eng.idle:
                    wake = [self.t1]
                    if nxt < len(plan):
                        wake.append(plan[nxt].due)
                    if self.trace and not tracing:
                        wake.append(tr0)
                    wake = min(wake)
                    with jax.profiler.TraceAnnotation(ANNOT + "wait"):
                        time.sleep(max(wake - now, 0.0))
                    continue
            else:
                self._resubmit_done()
            self._step()
        if tracing:
            self.trace_host = (t_tr, time.perf_counter())
            jax.profiler.stop_trace()
        jax.config.update("jax_log_compiles", False)
        jax.monitoring.unregister_event_duration_listener(listener)
        self.compiles_in_window = sum(
            1 for e in events if e == "/jax/core/compile/jaxpr_to_mlir_module_duration"
        )
        self.generator_late = [
            rec.submit - rec.due for rec in self.recs if self.open
        ]
        self.after_window()
        if self.trace_host is not None:
            from trace_reduce import load, reduce

            try:
                self.trace_red = reduce(load(str(tdir)))
            except ValueError as err:
                if self.require_tpu:
                    raise
                self.log(f"trace: {err}")     # the CPU has no device planes
            shutil.rmtree(tdir, ignore_errors=True)
        if self.tel is not None:
            self.tel_events = self.tel.tracer.events()
            for ev in self.tel_events:
                if ev.name == "admit" and ev.args and ev.args.get("req_id") in self.by_rid:
                    rec = self.by_rid[ev.args["req_id"]]
                    if rec.admit is None:
                        rec.admit = ev.ts + self.tel_offset

    def after_window(self):
        """Serve, with no new requests, until each request sent has its
        first token (at most ``drain_s``, counted from when the trace, if
        any, has been written); then drain the pipeline."""
        end = time.perf_counter() + self.mix["drain_s"]
        while (time.perf_counter() < end and not self.eng.idle
               and any(r.first is None and r.req is not None for r in self.recs)):
            self._step()
        self.eng.drain()

    def read_memory(self):
        stats = self.devices[0].memory_stats() or {}
        self.memory_peak = stats.get("peak_bytes_in_use")

    def free_engine(self):
        import gc

        self.eng = None
        for rec in self.recs:
            rec.req = None
        self.by_rid = {}
        gc.collect()

    # ------------------------------------------------------- correctness --

    def sample(self):
        fin = [r for r in self.recs if r.done is not None]
        if not fin:
            return []
        spec = self.mix["sample"]
        longest = max(fin, key=lambda r: (len(r.prompt) + r.max_new, -r.idx))
        rest = [r for r in fin if r is not longest]
        order = np.random.default_rng([self.seed, 9]).permutation(len(rest))
        out, n = [longest], longest.max_new
        for j in order:
            if n >= spec["tokens"] or len(out) >= spec["max_requests"]:
                break
            out.append(rest[j])
            n += rest[j].max_new
        return out

    def compare(self, control: bool = False):
        """Widest reference-logit gap of the served tokens of the sample;
        with ``control``, also that of the tokens the fp8 reference puts
        first at the same positions (the control's reading)."""
        import jax.numpy as jnp

        ref = load_reference(self.conf["reference"])
        lens = self.mix["prompt_len"]["max"], self.mix["output_len"]["max"]
        t_pad = math.ceil((lens[0] + lens[1]) / 256) * 256
        f32 = ref.make_reader(self.conf, t_pad, lens[1])
        fp8 = ref.make_reader(self.conf, t_pad, lens[1], fp8=True) if control else None
        served_gap, control_gap, n_tok = 0.0, 0.0, 0
        for rec in self.sample():
            p, n = len(rec.prompt), len(rec.tokens)
            seq = np.zeros(t_pad, np.int32)
            seq[:p] = rec.prompt
            seq[p:p + n] = rec.tokens
            served = np.zeros(lens[1], np.int32)
            served[:n] = rec.tokens
            args = (self.params, jnp.asarray(seq), jnp.int32(p - 1))
            gap, _ = f32(*args, jnp.asarray(served))
            served_gap = max(served_gap, float(np.max(np.asarray(gap)[:n])))
            n_tok += n
            if control:
                _, top = fp8(*args, jnp.asarray(served))
                gap8, _ = f32(*args, top)
                control_gap = max(control_gap, float(np.max(np.asarray(gap8)[:n])))
        self.sample_tokens = n_tok
        return served_gap, (control_gap if control else None)

    # ----------------------------------------------------------- results --

    def due_in_window(self):
        if self.open:
            return [r for r in self.recs if self.t0 <= r.due < self.t1]
        return list(self.recs)

    def metrics(self):
        wanted = self.per_layer if self.trace else self.end_to_end
        out = {}
        for m in wanted:
            v = load_reader(m["name"])(self)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out


def execute(name, seed, seconds, trace, **kw) -> dict:
    """A whole run; returns the result line as a dict."""
    run = Run(name, seed, seconds, trace, **kw)
    run.setup()
    run.window()
    run.read_memory()
    run.free_engine()
    due = run.due_in_window()
    failed = sum(1 for r in due if r.first is None)
    gap, _ = run.compare()
    limits = run.extra.get("limits", {})
    checks = {
        "logit_gap_max": {"value": gap, "limit": limits.get("logit_gap_max")},
        "requests_failed": {"value": failed, "limit": 0},
    }
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values()) and run.sample_tokens > 0
    dev = run.devices[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(run.devices), "memory_peak_bytes": run.memory_peak,
    }
    result = {
        "correct": bool(correct), "attempted": len(due), "failed": failed,
        "metrics": run.metrics(), "device": device,
    }
    if run.trace_red is not None:
        device["busy_s"] = run.trace_red["busy_s"]
        device["window_s"] = run.trace_red["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace_red["device_ops"],
            "idle_gaps": run.trace_red["idle_gaps"],
        }
    result["checks"] = checks
    run.log(
        f"window {run.seconds} s: {len(due)} requests attempted, {failed} failed, "
        f"{sum(len(r.times) for r in run.recs)} tokens, {run.k} steps, "
        f"compiles in window {run.compiles_in_window}, setup {run.setup_s:.3f} s"
    )
    if run.open and run.generator_late:
        late = sorted(run.generator_late)
        run.log(f"generator late: median {late[len(late) // 2] * 1e3:.3f} ms, "
                f"max {late[-1] * 1e3:.3f} ms over {len(late)} submits")
    run.log(f"correctness sample: {run.sample_tokens} served tokens")
    for k, c in checks.items():
        run.log(f"check {k}: {c['value']} limit {c['limit']}")
    return result
