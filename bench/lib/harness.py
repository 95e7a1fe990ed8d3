"""One run of one cell: build, warm up, measure, check, report.

``run`` is the whole of ``bench/run.py`` after the device check; tests
call it on the CPU with a tiny configuration.
"""
from __future__ import annotations

import dataclasses
import resource
import shutil
import sys
import time
from dataclasses import dataclass, field

from . import check, checkpoint, spans, spec
from .compile_clock import CompileClock
from .loop import ClosedLoop, null_span
from .traffic import Mix, longest

TRACE_DIR = spec.BENCH / ".trace"
# the program's activation width per element (bfloat16 pages)
KV_BYTES = 2


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Ctx:
    """What a run measured; the metric files read it."""

    model: dict
    reference: object       # the configuration's bench/reference module
    peaks: dict
    chips: int
    max_batch: int
    loop: ClosedLoop
    w0: float
    w1: float
    setup_s: float
    setup_compile_s: float
    hbm_peak_bytes: int
    weight_leaves: list
    paged: object
    trace: dict | None = None
    spans: dict | None = None   # lib/spans.read of the traced window
    kernels: dict = field(default_factory=dict)

    def kernel(self, name: str):
        if name not in self.kernels:
            self.kernels[name] = spec.module("kernels", name)
        return self.kernels[name]


# -- the program, as the configuration states it ---------------------------

def arch_config(config: dict, ref, max_len: int):
    """The program's ArchConfig for ``config``: the registry entry with the
    configuration's overrides, checked against every key of its ``model``
    section by the declaration of its reference ``ref``: a key the
    program reads (``PROGRAM_FIELDS``), a key whose value the program
    computes (``PROGRAM_FIXED``), the key of the longest sequence the
    model takes (``PROGRAM_CONTEXT``), which the traffic's ``max_len``
    must fit, and the program's layer as the reference's
    (``PROGRAM_LAYER``).  So the program runs what the file states, and a
    key no declaration covers is an error."""
    from repro.configs import get
    prog = config["program"]
    cfg = dataclasses.replace(get(prog["arch"]), **prog.get("overrides", {}))
    m = config["model"]
    fields, fixed = ref.PROGRAM_FIELDS, ref.PROGRAM_FIXED
    context = ref.PROGRAM_CONTEXT
    uncovered = sorted(set(m) - set(fields) - set(fixed) - {context})
    if uncovered:
        raise ValueError(
            f"configuration {config['name']}: model keys {uncovered} are "
            f"in none of PROGRAM_FIELDS, PROGRAM_FIXED and PROGRAM_CONTEXT "
            f"of its reference {config['reference']}")
    bad = [f"{k}: file {m.get(k)!r} vs program {a} {getattr(cfg, a)!r}"
           for k, a in fields.items() if m.get(k) != getattr(cfg, a)]
    bad += [f"{k}: file {m.get(k)!r}, the program computes {v!r}"
            for k, v in fixed.items() if m.get(k) != v]
    if context in m and m[context] < max_len:
        bad.append(f"{context}: file {m[context]!r}, the traffic asks for "
                   f"{max_len} tokens")
    bad += [f"program {a} {getattr(cfg, a)!r}, the reference's layer "
            f"needs {v!r}" for a, v in ref.PROGRAM_LAYER.items()
            if getattr(cfg, a) != v]
    if bad:
        raise ValueError(f"configuration {config['name']} and the program "
                         f"disagree: {'; '.join(bad)}")
    return cfg


def engine_config(config: dict, mix: Mix, cfg):
    """The engine options of ``config`` for the traffic ``mix``: the
    cache covers the mix's longest sequence; the raw page pool holds what
    the configuration's KV reckoning allows, at most the worst case, and
    the cold pool as many pages."""
    from repro.serving import EngineConfig
    e = config["engine"]
    ps = e["page_size"]
    max_len = -(-mix.max_len // ps) * ps
    page_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * ps * cfg.hd
                  * KV_BYTES)
    worst = e["max_batch"] * (max_len // ps) + 1
    n_pages = min(int(e["kv_pool_bytes"] // page_bytes), worst)
    return EngineConfig(
        max_batch=e["max_batch"], max_len=max_len, page_size=ps,
        cache_mode="paged", compress_cold=e["compress_cold"],
        n_pages=n_pages, n_cold_slots=n_pages - 1,
        prefill_chunk=e["prefill_chunk"],
        prefill_budget=e["prefill_budget"])


def weight_leaves(params) -> list:
    """(array shapes, meta, layers) of every compressed weight."""
    import jax
    from repro.core.store import is_compressed
    out = []
    for x in jax.tree_util.tree_leaves(params, is_leaf=is_compressed):
        if is_compressed(x):
            shapes = {k: tuple(a.shape) for k, a in x.arrays.items()}
            layers = shapes["payload"][0] if len(shapes["payload"]) == 4 \
                else 1
            out.append((shapes, x.meta, layers))
    return out


# -- one run ----------------------------------------------------------------

def run(bm: dict, workload: str, *, seed: int, seconds: float, trace: bool,
        t_start: float, device, peaks: dict, root=spec.ROOT,
        bench=spec.BENCH, control: bool = False) -> dict:
    """One run of ``workload``; ``control`` also reads the control's gap
    (``lib/check.py``) into ``result["control"]``."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving import GenerationEngine, Request

    cell = spec.cell(bm, workload)
    config = spec.config(bm, cell["config"], root)
    traffic = spec.traffic(cell["traffic"], bench)
    ref = spec.module("reference", config["reference"], bench)
    clock = CompileClock()
    log(f"cell {workload}: config {config['name']}, traffic "
        f"{cell['traffic']}, seed {seed}, {seconds} s, trace {int(trace)}; "
        f"compile cache {enable_compile_cache()}")

    cfg = arch_config(config, ref, longest(traffic))
    mix = Mix(traffic, seed, cfg.vocab_size)
    ecfg = engine_config(config, mix, cfg)
    tree, info = checkpoint.encoded_tree(cfg, ref, config, seed, log=log)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.device_put(tree, device))
    del tree
    log(f"weights on device: {time.perf_counter() - t0:.2f} s")
    eng = GenerationEngine(params, cfg, config=ecfg)
    log(f"engine: max_len {ecfg.max_len}, {eng.paged.n_pages} raw pages, "
        f"{eng.paged.n_cold} cold slots, max_batch {ecfg.max_batch}")

    span = jax.profiler.TraceAnnotation if trace else null_span
    loop = ClosedLoop(mix, eng, Request, span=span)
    t0 = time.perf_counter()
    loop.warm(ecfg.page_size)
    log(f"traffic warm-up: {len(loop.steps)} steps, "
        f"{time.perf_counter() - t0:.2f} s; programs compiled in set-up "
        f"{clock.compiles}")
    setup_compile_s = clock.seconds
    traces0, compiles0 = clock.traces, clock.compiles
    tdir = TRACE_DIR / workload
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    w_start = time.perf_counter()
    setup_s = w_start - t_start
    with span("bench.window"):
        w0, w1 = loop.run(seconds)
    if trace:
        jax.profiler.stop_trace()
    hbm = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.local_devices()[: cell["chips"]])
    log(f"window: {w1 - w0:.3f} s, {len(loop.steps)} steps in all; "
        f"programs traced in the window {clock.traces - traces0}, "
        f"compiled {clock.compiles - compiles0}")

    ctx = Ctx(model=config["model"], reference=ref, peaks=peaks,
              chips=cell["chips"],
              max_batch=ecfg.max_batch, loop=loop, w0=w0, w1=w1,
              setup_s=setup_s, setup_compile_s=setup_compile_s,
              hbm_peak_bytes=hbm, weight_leaves=weight_leaves(eng.params),
              paged=eng.paged)
    result = {"attempted": len(loop.records), "failed": 0}
    if trace:
        from . import trace as T
        t0 = time.perf_counter()
        ctx.trace = T.reduce(*T.read(str(tdir)))
        ctx.spans = spans.read(str(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.2f} s")
    metrics = {}
    for m in spec.metrics(bm, workload, trace):
        v = spec.module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    n_gaps = sum(max(len([t for t in r.times if w0 < t <= w1]) - 1, 0)
                 for r in loop.records)
    n_first = sum(1 for r in loop.records if r.times and w0 < r.times[0] <= w1)
    log(f"host peak RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} B")
    log(f"samples: {n_gaps} inter-token gaps, {n_first} first tokens, "
        f"{sum(1 for r in loop.records if r.req.done)} requests finished, "
        f"{len([s for s in loop.steps if s.t0 >= w0])} steps in the window")

    # the program's state goes before the reference runs; the weights
    # stay until their decode is checked
    records = [(r.prompt, list(r.req.out_tokens)) for r in loop.records]
    _delete((eng.cache, eng.last_tok, eng.last_logits))
    del eng
    result["correct"], checked, ctrl = correct(config, ref, traffic,
                                               records, seed, params, cfg,
                                               control)
    if control:
        result["control"] = ctrl
    result["metrics"] = metrics
    result["device"] = {"platform": device.platform,
                        "kind": device.device_kind, "count": cell["chips"],
                        "memory_peak_bytes": int(hbm)}
    if trace:
        result["device"].update(busy_s=ctx.trace["busy_s"],
                                window_s=ctx.trace["window_s"])
        result["breakdown"] = {
            "device_ops": [list(x) for x in ctx.trace["device_ops"]],
            "idle_gaps": [list(x) for x in ctx.trace["idle_gaps"]]}
    result["check"] = checked
    return result


def _delete(tree) -> None:
    import jax
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def correct(config: dict, ref, traffic: dict, records, seed: int, params,
            cfg, control: bool = False) -> tuple:
    """(correct, {name: {value, limit}}, control) for the served tokens
    against the reference ``ref`` and the served weights; ``params`` are
    freed here.  With ``control``
    the third item is the control's widest gap and its own verdict by the
    same limit, else None."""
    t0 = time.perf_counter()
    samples = check.sample(records, seed, traffic["check_requests"])
    weights = check.ReferenceWeights(ref, config["model"],
                                     config["weight_seed"], seed)
    lay, _ = checkpoint.layout(cfg)
    bad_bits = check.weight_mismatches(params, lay, weights)
    _delete(params)
    log(f"weight decode checked: {bad_bits} elements differ, "
        f"{time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    precisions = ("float32", "fp8") if control else ("float32",)
    read = check.gaps(ref, config["model"], weights, samples, precisions)
    weights.delete()
    limit = config["check"]["max_logit_gap"]
    n = sum(len(s[1]) for s in samples)
    log(f"reference: {len(samples)} requests, {n} served tokens, "
        f"{time.perf_counter() - t0:.2f} s")

    def judged(gaps):
        widest = max(gaps) if gaps else None
        return widest, widest is not None and widest <= limit

    widest, ok = judged(read["float32"])
    ctrl = None
    if control:
        c_gap, c_ok = judged(read["fp8"])
        ctrl = {"logit_gap": c_gap, "correct": c_ok}
    return (ok and bad_bits == 0,
            {"logit_gap": {"value": widest, "limit": limit},
             "weight_bit_mismatches": {"value": bad_bits, "limit": 0}},
            ctrl)
