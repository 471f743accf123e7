"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the result line.

Everything a cell is made of is found by name under the benchmark's root:
``BENCHMARK.json`` names the cell, its configuration file and its traffic
mix (``bench/traffic/<traffic>.json``); each metric is a small reader,
``bench/metrics/<metric>.py`` with ``read(run) -> float | None``, or, for
a dotted name such as ``device_idle.snapshot``, the reader of the part
before the first dot (``bench/metrics/device_idle.py``) where the full
name has none. A new cell, mix or metric is new files and entries, never
an edit here.

A configuration is a deployment of the graph store: an undirected Graph500
Kronecker graph made on the device from the seed (``graphgen``), loaded in
one epoch through ``GraphQueryServer.step`` into a WAL-backed
``ShardedDynamicGraph``. A traffic mix names its readers: closed-loop RPC
clients, each sending a query drawn from the mix and waiting for its
answer before sending the next. They talk over ``GraphRPCClient`` to a
``GraphRPCServer`` in this process, as a deployment's clients would.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Optional

import numpy as np

from bench import checks, graphgen, tracereduce

ROOT = pathlib.Path(__file__).resolve().parents[1]
# how long past the close of the window an answer is waited for
ANSWER_GRACE_S = 60.0
FRONTIER_KINDS = ("k_hop", "reachability")


# ------------------------------------------------------------ definitions
@dataclasses.dataclass(frozen=True)
class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""
    root: pathlib.Path
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    def metrics(self, trace: bool) -> list[dict]:
        """The metric entries this cell reports in a run with ``trace``."""
        entries = self.per_layer if trace else self.end_to_end
        return [m for m in entries
                if self.name in m.get("workloads", [self.name])]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(root, name, int(w["chips"]), config, traffic,
                spec["end_to_end"], spec["per_layer"])


def load_metric(root: pathlib.Path, name: str) -> Callable:
    folder = root / "bench" / "metrics"
    path = folder / f"{name}.py"
    if not path.exists():
        path = folder / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str) -> dict:
    """Peak rates of one chip of ``kind``; an unknown kind is an error."""
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peak rates for device kind {kind!r}; add them "
                       "to bench/peaks.json with their source")
    return table[kind]


def require_chips(chips: int) -> dict:
    """The device this run is on; exits when JAX finds no TPU or fewer
    chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


# ------------------------------------------------------------------ records
@dataclasses.dataclass
class Request:
    """One read as the client saw it."""
    query: object
    t_sent: float
    t_done: Optional[float] = None
    ok: bool = False
    epoch: Optional[int] = None        # stamped version's epoch
    value: object = None
    error: Optional[str] = None


@dataclasses.dataclass
class Run:
    """Everything the metric readers and the check read."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    device: dict
    peaks: dict
    graph: Optional[graphgen.Graph] = None
    setup_s: float = 0.0
    t_open: float = 0.0
    t_close: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    received_bytes: int = 0
    stats_before: dict = dataclasses.field(default_factory=dict)
    stats_after: dict = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    trace_summary: Optional[tracereduce.Summary] = None
    checked: Optional[checks.Checked] = None

    def answered(self, kind: Optional[str] = None) -> list[Request]:
        from repro.graph.query import query_kind
        return [r for r in self.requests if r.ok and (
            kind is None or query_kind(r.query) == kind)]


class CompileClock:
    """Counts JAX backend compiles."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


# ------------------------------------------------------------------ traffic
def make_query(entry: dict, n: int, rng: np.random.Generator,
               source: Optional[int] = None):
    """One query of a mix entry; vertices are drawn uniformly among the
    graph's ``n`` (non-isolated) vertices, as Graph500 draws BFS roots,
    unless ``source`` is given."""
    from repro.graph.query import KHop, Reachability
    kind = entry["kind"]
    if kind not in FRONTIER_KINDS:
        raise ValueError(f"unknown query kind {kind!r} in a traffic mix")
    a, b = rng.integers(0, n, 2)
    a = a if source is None else source
    if kind == "k_hop":
        return KHop(int(a), k=int(entry["k"]))
    return Reachability(int(a), int(b), max_hops=entry.get("max_hops"))


def draw_entry(mix: list, rng: np.random.Generator) -> dict:
    shares = np.asarray([e["share"] for e in mix], np.float64)
    return mix[int(rng.choice(len(mix), p=shares / shares.sum()))]


class CountingSocket:
    """A client's socket that counts the bytes received through it."""

    def __init__(self, sock):
        self.sock = sock
        self.received = 0

    def recv(self, size: int) -> bytes:
        chunk = self.sock.recv(size)
        self.received += len(chunk)
        return chunk

    def __getattr__(self, name):
        return getattr(self.sock, name)


def counting_client(address, timeout_s: float):
    """A ``GraphRPCClient`` on ``address`` whose socket counts what it
    receives (the client only reconnects after a failed round trip, which
    ends a reader here)."""
    from repro.launch.rpc import GraphRPCClient
    client = GraphRPCClient(*address, timeout_s=timeout_s)
    client._sock = CountingSocket(client._sock)
    return client


# --------------------------------------------------------------- deployment
class Deployment:
    """The system under test: store, query server and RPC front."""

    def __init__(self, config: dict, graph: graphgen.Graph, wal_dir: str):
        from repro.graph.sharded import RoutingPlan, ShardedDynamicGraph
        from repro.launch.rpc import GraphRPCServer
        from repro.launch.serve_graph import GraphQueryServer
        store = config["store"]
        shards = int(store["shards"])
        # each shard's capacity: the rows the store's own route sends it
        per_shard = np.bincount(RoutingPlan.initial(shards).assign(
            graph.dst), minlength=shards)
        e_max = int(per_shard.max()) + 1024
        self.graph = graph
        self.wal_dir = wal_dir
        self.store = ShardedDynamicGraph(
            shards, graph.n, e_max, wal_dir=wal_dir,
            wal_fsync=store["wal_fsync"], parallel_apply=shards)
        self.server = GraphQueryServer(self.store)
        self.rpc = GraphRPCServer(
            self.server, batch_wait_s=float(config["rpc"]["batch_wait_s"]))

    def load(self) -> None:
        """The whole graph as epoch 0, in one ``server.step``."""
        from repro.core.versioned import Version
        from repro.graph.dyngraph import MutationBatch
        batch = MutationBatch(Version(0, 0), add_src=self.graph.src,
                              add_dst=self.graph.dst)
        self.server.step(batch)
        if self.server.latest_version() != batch.version:
            raise RuntimeError("the load epoch was not published")
        self.rpc.start()

    def stats(self) -> dict:
        return dataclasses.asdict(self.server.stats())

    def close(self) -> None:
        """Stop every thread and close every file of the system."""
        self.rpc.stop()
        self.server.stop_prewarm()
        for w in [*self.store.wal_shards, self.store.wal]:
            if w is not None:
                w.close()
        self.store.shutdown()


# ------------------------------------------------------------------ warm-up
def routed_shards(dep: Deployment, hops: Optional[int]) -> np.ndarray:
    """Per source vertex, the bit mask of the shards a ``hops``-step
    frontier expansion from it reads rows of (``None``: until the frontier
    drains): those that hold an out-edge of a vertex within ``hops - 1``
    steps. Rows live on the shard of their destination."""
    g = dep.graph
    order = np.argsort(g.src, kind="stable")
    nbr = g.dst[order]
    deg = np.bincount(g.src, minlength=g.n)
    off = np.concatenate([[0], np.cumsum(deg)[:-1]])
    bits = np.left_shift(np.int64(1), dep.store.route(nbr))
    own = np.where(deg > 0, np.bitwise_or.reduceat(bits, off), 0)
    mask = own
    for _ in range(g.n if hops is None else hops - 1):
        wider = own | np.where(
            deg > 0, np.bitwise_or.reduceat(mask[nbr], off), 0)
        if np.array_equal(wider, mask):
            break
        mask = wider
    return mask


def warm_up(dep: Deployment, traffic: dict, rng: np.random.Generator,
            started: float) -> None:
    """Run each shape the window will use once, so nothing compiles in it.

    Frontier kinds (k-hop, reachability) are batched per window; the
    window's size and the edge subset it is routed to set the shapes of
    their programs. They are warmed at every window size the readers can
    form on a small graph with the same vertex count (the programs that
    depend on the size alone), and on the deployment at the widths the
    sweep pads a window to (powers of two, ``graph/compute.py``) from
    sources whose expansion reads every shard. The routed subset is the
    full rows of each shard read, padded to a power of two, so subsets of
    one and of two shards have shapes of their own: one source of each
    warms them (a window of one is the closed loop's straggler; a window
    of several sources that all read the same one or two shards is too
    rare to warm)."""
    server = dep.server
    n = dep.graph.n
    for reader in traffic["readers"]:
        widest = int(reader["clients"])
        for entry in reader["mix"]:
            fresh = iter(rng.permutation(n))
            with small_server(dep) as side:
                for size in range(1, widest + 1):
                    run_window(side, [
                        make_query(entry, n, rng, source=int(next(fresh)))
                        for _ in range(size)])
            mask = routed_shards(dep, entry.get("k", entry.get("max_hops")))
            reads = np.bitwise_count(mask.astype(np.uint64))
            everything = rng.permutation(np.flatnonzero(
                reads == dep.store.n_shards))
            # distinct sources in every window: a repeated one is a hit
            # of the result cache and would narrow the swept width
            used = 0
            width = 1
            while True:
                run_window(server, [
                    make_query(entry, n, rng, source=int(v))
                    for v in everything[used:used + width]])
                used += width
                note(f"warmed {entry} at width {width}", started)
                if width >= widest:
                    break
                width *= 2
            for count in (1, 2):
                few = np.flatnonzero(reads == count)
                if few.size:
                    run_window(server, [make_query(
                        entry, n, rng, source=int(rng.choice(few)))])
                    note(f"warmed {entry} on {count} shard(s)", started)


def run_window(server, queries) -> None:
    """Answer ``queries`` as one window of ``server``, in this thread."""
    from repro.graph.query import QueryRequest
    done = []
    for i, q in enumerate(queries):
        server.submit_request(QueryRequest(query=q, request_id=i),
                              on_done=done.append)
    server.run_window()
    bad = [r for r in done if not r.ok]
    if len(done) != len(queries) or bad:
        raise RuntimeError(f"warm-up window failed: {bad[:1]}")


class small_server:
    """A query server over the first rows of the deployment's graph, with
    its vertex count and shard count (a context manager)."""

    def __init__(self, dep: Deployment):
        self.dep = dep

    def __enter__(self):
        from repro.core.versioned import Version
        from repro.graph.dyngraph import MutationBatch
        from repro.graph.sharded import ShardedDynamicGraph
        from repro.launch.serve_graph import GraphQueryServer
        g = self.dep.graph
        rows = min(g.m, 4096)
        self.store = ShardedDynamicGraph(self.dep.store.n_shards, g.n, rows)
        self.server = GraphQueryServer(self.store)
        self.server.step(MutationBatch(Version(0, 0), add_src=g.src[:rows],
                                       add_dst=g.dst[:rows]))
        return self.server

    def __exit__(self, *exc):
        self.server.stop_prewarm()
        self.store.shutdown()


# ------------------------------------------------------------------- window
def closed_reader(run: Run, address, reader: dict, t_end: float,
                  seed: int, lock: threading.Lock) -> list[Callable]:
    """One thread per client: draw a query, send it, wait for its answer,
    until ``t_end``."""
    import jax
    n = run.graph.n

    def client(i: int) -> None:
        rng = np.random.default_rng([seed, 1, i])
        cli = counting_client(address, ANSWER_GRACE_S + 600)
        sock = cli._sock
        try:
            while time.perf_counter() < t_end:
                q = make_query(draw_entry(reader["mix"], rng), n, rng)
                req = Request(q, t_sent=time.perf_counter())
                try:
                    with jax.profiler.TraceAnnotation("bench.send"):
                        cli.send(q)
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        resp = cli.recv()
                except (OSError, ConnectionError) as exc:
                    req.error = f"transport: {exc}"
                    with lock:
                        run.requests.append(req)
                    break
                req.t_done = time.perf_counter()
                req.ok = bool(resp.ok)
                if resp.ok:
                    req.value = resp.value
                    req.epoch = (resp.version.epoch
                                 if resp.version is not None else None)
                else:
                    req.error = resp.error.code
                with lock:
                    run.requests.append(req)
        finally:
            with lock:
                run.received_bytes += sock.received
            cli.close()

    return [functools.partial(client, i)
            for i in range(int(reader["clients"]))]


def _catching(target: Callable, errors: list) -> Callable:
    def run() -> None:
        try:
            target()
        except BaseException as exc:    # re-raised by measure()
            errors.append(exc)
    return run


def measure(run: Run, dep: Deployment, clock: CompileClock) -> None:
    """The measured window: every client sends from the open for
    ``run.seconds``; the window closes when the last answer in flight is
    in."""
    import jax
    lock = threading.Lock()
    run.stats_before = dep.stats()
    compiles_before = clock.count
    t_open = time.perf_counter()
    t_end = t_open + run.seconds
    targets = []
    for i, reader in enumerate(run.cell.traffic["readers"]):
        targets += closed_reader(run, dep.rpc.address, reader, t_end,
                                 run.seed * 16 + i, lock)
    errors: list = []
    threads = [threading.Thread(target=_catching(t, errors))
               for t in targets]
    with jax.profiler.TraceAnnotation("bench.window"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    if errors:
        raise RuntimeError("a load thread failed") from errors[0]
    run.t_open = t_open
    run.t_close = max([t_open] + [r.t_done for r in run.requests
                                  if r.t_done])
    run.compiles_in_window = clock.count - compiles_before
    run.stats_after = dep.stats()


# --------------------------------------------------------------------- main
def note(what: str, started: float) -> None:
    """A progress line on standard error: seconds since the process
    started and the host memory it holds."""
    import psutil
    rss = psutil.Process().memory_info().rss / 2**30
    print(f"bench: {time.time() - started:8.1f} s  {rss:5.1f} GiB  {what}",
          file=sys.stderr, flush=True)


def process_start_time() -> float:
    """Wall-clock time at which this process started."""
    import psutil
    return psutil.Process().create_time()


def enable_cache() -> None:
    """JAX's persistent compilation cache in the program's directory for
    it, holding every program, small ones too, so that only the first run
    of a cell in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            device: dict, started: float) -> Run:
    """Set up, measure and check one run of ``cell``."""
    import jax
    enable_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    run = Run(cell, seed, seconds, trace, device, device_peaks(device["kind"]))
    run.graph = graphgen.generate(seed, cell.config["graph"])
    note(f"graph of {run.graph.n} vertices and {run.graph.m} rows", started)
    scratch = tempfile.mkdtemp(prefix="bench_")
    dep = None
    try:
        dep = Deployment(cell.config, run.graph,
                         str(pathlib.Path(scratch) / "wal"))
        dep.load()
        note(f"loaded; {dep.stats()['mirrored_vertices']} mirrored "
             "vertices", started)
        warm_up(dep, cell.traffic, np.random.default_rng([seed, 0]),
                started)
        gc.collect()
        note("warmed up", started)
        run.setup_s = time.time() - started
        tracer = (tracereduce.Tracer(pathlib.Path(scratch) / "trace")
                  if trace else None)
        if tracer:
            tracer.start()
        measure(run, dep, clock)
        if tracer:
            run.trace_summary = tracer.stop()
        stats = jax.devices()[0].memory_stats() or {}
        run.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        note(f"window closed: {len(run.requests)} requests, "
             f"{run.compiles_in_window} compiles, fan-out "
             f"{run.stats_after['fanout_hist']}", started)
        dep.close()
        dep = None
        gc.collect()
        run.checked = checks.check(run, str(pathlib.Path(scratch) / "wal"))
        note("checked", started)
    finally:
        if dep is not None:
            dep.close()
        shutil.rmtree(scratch, ignore_errors=True)
    return run


def result_line(run: Run) -> dict:
    cell = run.cell
    metrics = {}
    for entry in cell.metrics(run.trace):
        name = entry["name"]
        value = (run.setup_s if name == "setup_s"
                 else load_metric(cell.root, name)(run))
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}
    device = {"platform": run.device["platform"],
              "kind": run.device["kind"], "count": run.device["count"],
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": run.checked.correct, "attempted": len(run.requests),
           "failed": sum(not r.ok for r in run.requests),
           "metrics": metrics, "device": device}
    if run.trace_summary is not None:
        device["busy_s"] = run.trace_summary.busy_s
        device["window_s"] = run.trace_summary.window_s
        out["breakdown"] = run.trace_summary.breakdown
    out["checks"] = run.checked.as_dict()
    return out


def main(argv=None, *, root: pathlib.Path = ROOT,
         gate: Callable[[int], dict] = require_chips) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    started = process_start_time()
    cell = load_cell(root, args.workload)
    device = gate(cell.chips)
    run = execute(cell, args.seed, args.seconds, bool(args.trace), device,
                  started)
    line = result_line(run)
    for name, c in run.checked.as_dict().items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
