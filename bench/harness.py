"""One run of one cell, from the seed to the result line.

Set-up generates the cell's corpus from the seed, builds the index through
``Retriever.build`` and answers one request (``build_s`` times that span,
less the compile seconds inside it), then warms the batch size the
traffic will flush (``max_batch``) through ``Retriever.search``. The
window drives the cell's traffic through ``SearchServer.submit``. After it
the program's state is freed, its index is checked and every answer of the
window is judged against the plain reference (``bench/reference.py``);
recall is read against exact search. With ``control`` the reference's
control takes the program's place in that judgement: its answers and its
assignment are judged in place of the program's, so ``correct`` reads
false where the control is caught.

With ``trace`` the build span and the window are recorded by the profiler
and the cell's per-layer metrics are read from them by their readers
(``bench/metrics/<name>.py``); otherwise the end-to-end metrics are
reported. Nothing here is specific to a cell: the configuration, the
traffic and the metrics come by name from ``bench/spec.py``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import reference, spec, traffic as traffic_gen
from .clock import CompileClock
from .corpus import make_corpus
from .loops import closed_loop


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader reads."""

    window_s: float
    responses: list           # the program's responses answered in window
    dispatches: int           # engine dispatches the server made in window
    trace: object             # bench.xplane.Trace of the window
    window_ns: tuple          # the window on the trace's clock
    build_trace: object       # bench.xplane.Trace of the build span
    build_ns: tuple
    dispatch_work: list       # per dispatch: [(probes, n_scored)] per request
    counts: np.ndarray        # live members per bucket, flat (T*K,)
    d: int
    itemsize: int
    n_leaders: int            # T*K
    peaks: dict
    n_devices: int


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed ``.jax_cache`` of the checkout (the path is part
    of the cache's key). Every program is kept, so that a second run of a
    cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(spec.ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Profiler:
    """The profiler around a span, on only when the run traces."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dirs: list[str] = []

    def start(self, name: str):
        if not self.enabled:
            return None
        import jax

        d = tempfile.mkdtemp(prefix=f"bench-{name}-")
        self.dirs.append(d)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        ann.__enter__()
        return ann, d

    def stop(self, handle):
        """Close the span and read its trace (None when not tracing)."""
        if handle is None:
            return None
        import jax

        from .xplane import Trace

        ann, d = handle
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return Trace.from_dir(d)

    def cleanup(self):
        for d in self.dirs:
            shutil.rmtree(d, ignore_errors=True)


def _devices(n_chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
        if len(devs) < n_chips:
            raise NoChip(f"the cell needs {n_chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, control: bool = False,
        cell: dict | None = None) -> dict:
    """One run; returns the result line as a dict. ``cell`` (tests only)
    gives ``{"config": ..., "traffic": ..., "workload": ...}`` in place of
    the files named by ``workload``."""
    if cell is None:
        w = spec.workload(workload)
        cell = {"workload": w, "config": spec.config(w["config"]),
                "traffic": spec.traffic(w["traffic"])}
    w, cfg, tr = cell["workload"], cell["config"], cell["traffic"]
    traffic_gen.validate(tr)
    if require_chip:
        configure_compile_cache()
    devs = _devices(int(w["chips"]), require_chip)
    used = devs[: int(w["chips"])]
    kind = devs[0].device_kind
    peaks = spec.peaks(kind) if require_chip else None
    log(f"device: platform={devs[0].platform} kind={kind} count={len(devs)} "
        f"cell={w['name']} seed={seed} seconds={seconds} trace={int(trace)}")
    prof = Profiler(trace)
    try:
        return _run(w, cfg, tr, seed, seconds, trace, t_start=t_start,
                    devs=devs, used=used, peaks=peaks, prof=prof,
                    control=control)
    finally:
        prof.cleanup()


class Session:
    """A cell's index built from the seed and warmed up, with its server:
    what a window drives. ``build_s`` and the build span's trace are taken
    here."""

    def __init__(self, cfg: dict, tr: dict, seed: int, used, prof, clock):
        import jax

        from repro.core import FieldSpec, Retriever, SearchRequest

        self.cfg, self.seed = cfg, seed
        corpus, ix = cfg["corpus"], cfg["index"]
        self.names = tuple(corpus["field_names"])
        self.dims = tuple(corpus["field_dims"])
        self.SearchRequest = SearchRequest
        n_docs = int(corpus["n_docs"])

        t0 = time.perf_counter()
        self.docs_np = make_corpus(corpus, seed)
        self.docs = jax.block_until_ready(
            jax.device_put(self.docs_np))
        log(f"corpus: {self.docs_np.shape} float32 from the seed in "
            f"{time.perf_counter() - t0:.3f} s")

        warm = traffic_gen.RequestStream(tr, seed, n_docs, len(self.names),
                                         traffic_gen.WARM_STREAM)
        pack = None if ix["pack_dtype"] == "float32" else ix["pack_dtype"]
        handle = prof.start("build")
        c_sec, c_n, c_hit = clock.reading()
        t0 = time.perf_counter()
        self.retriever = Retriever.build(
            self.docs, FieldSpec(names=self.names, dims=self.dims),
            int(ix["k_clusters"]), n_clusterings=int(ix["n_clusterings"]),
            method=ix["method"], backend=ix["backend"], pack_dtype=pack,
            key=jax.random.PRNGKey(seed),
        )
        self.retriever.search(self.request(warm[0]))
        build_wall = time.perf_counter() - t0
        b_sec, b_n, b_hit = clock.reading()
        self.build_trace = prof.stop(handle)
        self.build_s = build_wall - (b_sec - c_sec)
        index = self.retriever.index
        t_cl, k_cl, b_cl = (int(x) for x in index.buckets.shape)
        log(f"build: clusterer={index.method} "
            f"backend={self.retriever.backend} T={t_cl} K={k_cl} B={b_cl}; "
            f"{build_wall:.6f} s from build to first answer, of which XLA "
            f"compiles {b_sec - c_sec:.6f} s ({b_n - c_n} programs, "
            f"{b_hit - c_hit} persistent-cache hits): "
            f"build_s={self.build_s:.6f}")

        self.server = self.new_server()
        size = self.server.max_batch
        t0 = time.perf_counter()
        self.retriever.search([self.request(warm[1 + j])
                               for j in range(size)])
        w_sec, w_n, w_hit = clock.reading()
        log(f"warm-up: batch size {size} "
            f"in {time.perf_counter() - t0:.3f} s, XLA compiles "
            f"{w_sec - b_sec:.3f} s ({w_n - b_n} programs, "
            f"{w_hit - b_hit} persistent-cache hits); "
            f"max_batch={self.server.max_batch}")
        self.stream = traffic_gen.RequestStream(tr, seed, n_docs,
                                                len(self.names))

    def new_server(self):
        from repro.serving import SearchServer

        srv = self.cfg["server"]
        return SearchServer(self.retriever, window_s=float(srv["window_s"]),
                            replicas=int(srv["replicas"]))

    def request(self, r):
        """The program's request for a generated one."""
        return self.SearchRequest(
            like=r.like, weights=dict(zip(self.names, map(float, r.weights))),
            k=r.k, probes=r.probes,
        )

    def drive(self, tr: dict, seconds: float, on_window) -> tuple:
        """One window of ``tr``'s loop through a fresh server (``server``
        until the next window). Returns ``(records, window_start,
        window_end, window_records)``: those answered inside the window."""
        from repro.serving import ServingError

        server = self.server = self.new_server()

        def make(i):
            return self.request(self.stream[i])

        async def serve():
            await server.start()
            try:
                return await closed_loop(
                    server, make, clients=int(tr["clients"]),
                    ramp_s=float(tr["ramp_s"]), seconds=seconds,
                    errors=(ServingError,), on_window=on_window)
            finally:
                await server.stop()

        records, w0, w1 = asyncio.run(serve())
        return records, w0, w1, [r for r in records if w0 <= r.done < w1]


def _run(w, cfg, tr, seed, seconds, trace, *, t_start, devs, used, peaks,
         prof, control):
    clock = CompileClock()
    ses = Session(cfg, tr, seed, used, prof, clock)
    retriever = ses.retriever
    names, dims, ix = ses.names, ses.dims, cfg["index"]
    docs, docs_np, stream = ses.docs, ses.docs_np, ses.stream
    index = retriever.index
    t_cl, k_cl = (int(x) for x in index.buckets.shape[:2])
    build_trace, build_s = ses.build_trace, ses.build_s
    marks: dict = {}

    def on_window(event):
        if event == "open":
            marks["t_open"] = time.perf_counter()
            marks["clock0"] = clock.reading()
            marks["batches0"] = ses.server.stats.batches
            marks["trace"] = prof.start("window")
        else:
            marks["wtrace"] = prof.stop(marks["trace"])
            marks["clock1"] = clock.reading()
            marks["batches1"] = ses.server.stats.batches

    records, w0, w1, window = ses.drive(tr, seconds, on_window)
    server = ses.server
    del ses
    setup_s = marks["t_open"] - t_start
    stats = server.stats.snapshot()
    in_window = marks["clock1"][1] - marks["clock0"][1]
    log(f"window: {w1 - w0:.6f} s; XLA compiles inside it: {in_window} "
        f"({marks['clock1'][0] - marks['clock0'][0]:.6f} s)")
    log("server: " + " ".join(
        f"{k}={stats[k]}" for k in (
            "submitted", "completed", "batches", "mean_batch_size",
            "expired", "rejected", "shed", "failed", "timeouts", "retries",
            "hedges", "hedge_wins", "degraded", "breaker_trips",
            "budget_exhausted")))

    attempted = len(window)
    answered = [r for r in window if r.response is not None]
    missing = attempted - len(answered)
    errors = [r.error for r in window if r.error]
    if errors:
        log(f"failed: {len(errors)} requests of the window, first: {errors[0]}")
    degraded = sum(bool(r.response.degraded) for r in answered)

    memory_peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in used
    )
    leaders = np.asarray(index.leaders)
    buckets = np.asarray(index.buckets)
    counts = np.asarray(index.counts)
    itemsize = int(np.dtype(ix["pack_dtype"]).itemsize)
    wtrace = marks.get("wtrace")
    window_ns = wtrace.span("bench.window") if wtrace is not None else None
    build_ns = (build_trace.span("bench.build")
                if build_trace is not None else None)
    del retriever, server, index, on_window
    gc.collect()

    # ---- the reference, once the program's state is freed ---------------
    t0 = time.perf_counter()
    ix_check = reference.check_index(docs, docs_np, leaders, buckets,
                                     counts, seed, control=control)
    judged = _judge(docs, leaders, buckets, dims, names,
                    [(stream[r.index], r.response) for r in answered],
                    control)
    ref_s = time.perf_counter() - t0
    log(f"reference: {len(answered)} answers and the index checked in "
        f"{ref_s:.3f} s; leader spread {ix_check['spread']} against the "
        f"reference FPF's {ix_check['reference_spread']}")

    program = {"answer_gap": judged["gap"],
               "assign_gap": ix_check["assign_gap"]}
    if control:
        log(f"control: the reference at {reference.CONTROL} answers and "
            f"assigns at {reference.FP8} in the program's place; the "
            f"program read {program}")
    values = {
        "answer_gap": judged["control_gap" if control else "gap"],
        "assign_gap": ix_check[
            "control_assign_gap" if control else "assign_gap"],
        "leader_gap": ix_check["leader_gap"],
        "missing": missing,
        "index_faults": ix_check["faults"],
    }
    limits = cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    correct = len(answered) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(missing)}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if not trace:
        values = {
            "qps": len(answered) / (w1 - w0),
            "recall": judged["recall"],
            "build_s": build_s,
            "setup_s": setup_s,
        }
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec.metrics_for(w["name"], "end_to_end")
        }
    else:
        reading = Reading(
            window_s=(window_ns[1] - window_ns[0]) / 1e9,
            responses=[r.response for r in answered],
            dispatches=marks["batches1"] - marks["batches0"],
            trace=wtrace, window_ns=window_ns, build_trace=build_trace,
            build_ns=build_ns,
            dispatch_work=_dispatch_work(answered, judged["probes"]),
            counts=counts.reshape(-1), d=int(sum(dims)), itemsize=itemsize,
            n_leaders=t_cl * k_cl, peaks=peaks, n_devices=len(used),
        )
        metrics = {}
        for m in spec.metrics_for(w["name"], "per_layer"):
            value = spec.metric_reader(m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = wtrace.busy_s(*window_ns)
        device["window_s"] = reading.window_s
        result["breakdown"] = {
            "device_ops": wtrace.top_modules(*window_ns),
            "idle_gaps": wtrace.idle_gaps(*window_ns),
        }
    result["metrics"] = metrics
    result["device"] = device
    if degraded:
        log(f"note: {degraded} answers in the window came back degraded")
    if control:
        result["program"] = program
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result


def _judge(docs, leaders, buckets, dims, names, answered,
           control: bool) -> dict:
    """Every ``(request, response)`` of the window against the reference,
    in blocks of one shape: the widest gap, the mean recall, and the probes
    the reference navigated to (the roofline's bytes)."""
    import jax.numpy as jnp

    lead, bkt = jnp.asarray(leaders), jnp.asarray(buckets)
    t = int(leaders.shape[0])
    gaps = np.zeros((len(answered),))
    cgaps = np.zeros((len(answered),))
    rec = np.zeros((len(answered),))
    probes: list = [None] * len(answered)
    shapes: dict = {}
    for i, (req, _) in enumerate(answered):
        shapes.setdefault((req.k, req.probes), []).append(i)
    blk = reference.BLOCK
    for (k, p), rows in shapes.items():
        probes_t = reference.split_probes(p, t)
        for a in range(0, len(rows), blk):
            part = rows[a:a + blk]
            idx = part + [part[-1]] * (blk - len(part))   # one block size
            reqs = [answered[i][0] for i in idx]
            resp = [answered[i][1] for i in idx]
            likes = jnp.asarray([q.like for q in reqs], jnp.int32)
            wts = jnp.asarray(np.stack([q.weights for q in reqs]))
            ids = np.stack([x.doc_ids for x in resp]).astype(np.int32)
            sc = np.stack([x.scores for x in resp]).astype(np.float32)
            fl = np.zeros(ids.shape + (len(names),), np.float32)
            for j, x in enumerate(resp):
                for c, h in enumerate(x.hits):
                    fl[j, c] = [h.field_scores[nm] for nm in names]
            ps, _, ei, rp, rf, flat = reference.truth(
                docs, lead, bkt, likes, wts, jnp.asarray(ids),
                probes_t=probes_t, k=k, dims=dims)
            n = len(part)
            excl = np.asarray(likes)
            gaps[part] = reference.answer_gaps(ids, sc, fl, excl, ps, rp,
                                               rf)[:n]
            rec[part] = reference.recall(ids, ei)[:n]
            flat = np.asarray(flat)
            for j, i in enumerate(part):
                probes[i] = flat[j]
            if control:
                cs, ci, cf = reference.control_answer(
                    docs, lead, bkt, likes, wts, probes_t=probes_t, k=k,
                    dims=dims)
                ps2, _, _, rp2, rf2, _ = reference.truth(
                    docs, lead, bkt, likes, wts, ci, probes_t=probes_t,
                    k=k, dims=dims)
                cgaps[part] = reference.answer_gaps(ci, cs, cf, excl, ps2,
                                                    rp2, rf2)[:n]
    return {
        "gap": float(gaps.max()) if gaps.size else 0.0,
        "recall": float(rec.mean()) if rec.size else 0.0,
        "probes": probes,
        "control_gap": float(cgaps.max()) if cgaps.size else 0.0,
    }


def _dispatch_work(answered, probes) -> list:
    """Group the window's answers into the dispatches that served them: the
    server stamps one ``compute_s`` on every rider of a dispatch."""
    groups: dict = {}
    for r, p in zip(answered, probes):
        key = (r.response.compute_s, r.response.batch_size)
        groups.setdefault(key, []).append((p, r.response.n_scored))
    return list(groups.values())
