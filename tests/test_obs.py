"""The archive's spans and counters (``repro.obs``): the table, the
layers that record into it, and the trace events they leave."""

from __future__ import annotations

import glob
import http.client
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.catalog import Catalog
from repro.etl import generate_raw_archive, ingest
from repro.serve.http import ArchiveServer, ArchiveService
from repro.store import ObjectStore, Repository

SRC = Path(__file__).resolve().parents[1] / "src"
VCP = "VCP-212"


def _delta(before, after):
    out = {}
    for name, row in after.items():
        old = before.get(name, {"n": 0, "s": 0.0, "bytes": 0})
        if row["n"] != old["n"]:
            out[name] = {k: row[k] - old[k] for k in ("n", "s", "bytes")}
    return out


class _Window:
    """What the table gains inside a ``with`` block.  A handled request's
    span closes after its client has the last byte, so ``requests`` says
    how many ``http.request`` spans to wait for."""

    def __init__(self, requests: int = 0):
        self._requests = requests

    def __enter__(self):
        self._before = obs.snapshot()
        return self

    def __exit__(self, *exc):
        deadline = time.monotonic() + 30.0
        while True:
            self.spans = _delta(self._before, obs.snapshot())
            done = self.spans.get("http.request", {"n": 0})["n"]
            if done >= self._requests or time.monotonic() > deadline:
                break
            time.sleep(0.005)


def _get(server, path, headers=None):
    host, port = server.address
    conn = http.client.HTTPConnection(host, port, timeout=60)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    base = tmp_path_factory.mktemp("obs")
    cat = Catalog.create(str(base / "catalog"))
    raw = ObjectStore(str(base / "raw"))
    generate_raw_archive(raw, site_id="KVNX", n_scans=5, n_az=24,
                         n_gates=280, n_sweeps=1, seed=3)
    ingest(raw, Repository.create(str(base / "store")), batch_size=5,
           time_chunk=2, catalog=cat, repo_id="KVNX")
    return cat


# -- the table -----------------------------------------------------------

def test_nested_spans_add_to_the_table():
    with _Window() as w:
        with obs.span("test.outer", nbytes=10, kind="x"):
            for _ in range(3):
                with obs.span("test.inner") as inner:
                    inner.nbytes = 4
        obs.record("test.handoff", 0.25, nbytes=7)
    assert w.spans["test.outer"]["n"] == 1
    assert w.spans["test.outer"]["bytes"] == 10
    assert w.spans["test.inner"]["n"] == 3
    assert w.spans["test.inner"]["bytes"] == 12
    assert 0.0 <= w.spans["test.inner"]["s"] <= w.spans["test.outer"]["s"]
    assert w.spans["test.handoff"] == {"n": 1, "s": 0.25, "bytes": 7}


def test_traced_part_holds_only_what_a_profiler_session_saw(tmp_path):
    import jax

    with obs.span("test.untraced"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test.traced", nbytes=2):
            pass
        obs.record("test.traced", 0.5)
    finally:
        jax.profiler.stop_trace()
    with obs.span("test.traced"):
        pass
    traced, everything = obs.snapshot(traced=True), obs.snapshot()
    assert "test.untraced" not in traced
    assert traced["test.traced"]["n"] == 2
    assert traced["test.traced"]["bytes"] == 2
    assert traced["test.traced"]["s"] >= 0.5
    assert everything["test.traced"]["n"] == 3


def test_a_span_that_raises_still_counts():
    with _Window() as w:
        with pytest.raises(ValueError):
            with obs.span("test.raises"):
                raise ValueError("boom")
    assert w.spans["test.raises"]["n"] == 1


def test_snapshot_is_consistent_under_eight_threads():
    n_threads, per_thread = 8, 400
    start = threading.Barrier(n_threads + 1)
    seen = []

    def work(i):
        start.wait()
        for _ in range(per_thread):
            with obs.span("test.threads.outer", nbytes=3):
                with obs.span("test.threads.inner", nbytes=5, thread=i):
                    pass
            obs.record("test.threads.record", 0.001, nbytes=2)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)     # switch threads as often as possible
    try:
        with _Window() as w:
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            start.wait(timeout=30)
            while any(t.is_alive() for t in threads):
                seen.append(obs.snapshot())
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    total = n_threads * per_thread
    assert w.spans["test.threads.outer"]["n"] == total
    assert w.spans["test.threads.outer"]["bytes"] == 3 * total
    assert w.spans["test.threads.inner"]["bytes"] == 5 * total
    assert w.spans["test.threads.record"]["bytes"] == 2 * total
    # every snapshot taken while the threads ran is a consistent cut of
    # each row: its count and its bytes moved together
    for snap in seen:
        row = snap.get("test.threads.record")
        if row is not None:
            assert row["bytes"] == 2 * row["n"]


def test_imports_and_counts_without_jax():
    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"
        "sys.modules['jax.profiler'] = None\n"
        "from repro import obs\n"
        "with obs.span('a', nbytes=3, kind='x'):\n"
        "    obs.annotate(route='/r')\n"
        "    with obs.span('b'):\n"
        "        pass\n"
        "obs.record('c', 0.5)\n"
        "s = obs.snapshot()\n"
        "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n"
        "assert s['a']['n'] == 1 and s['a']['bytes'] == 3, s\n"
        "assert s['b']['n'] == 1 and s['c']['s'] == 0.5, s\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          env=dict(os.environ, PYTHONPATH=str(SRC)),
                          timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()
    assert proc.stdout.strip() == b"ok"


# -- the store read path -------------------------------------------------

@pytest.mark.parametrize("cache_bytes", [1 << 30, 1],
                         ids=["prefetched", "demand"])
def test_array_read_records_get_decode_and_assemble(tmp_path, cache_bytes):
    repo = Repository.create(str(tmp_path / "repo"))
    data = np.random.default_rng(1).standard_normal((9, 17, 31)) \
        .astype("float32")
    tx = repo.writable_session()
    tx.create_array("x", shape=data.shape, dtype="float32",
                    chunks=(4, 8, 16)).write_full(data)
    tx.commit("write")
    session = repo.readonly_session(cache_bytes=cache_bytes)
    arr = session.array("x")
    fetches0 = session.cache_stats()["chunk_fetches"]
    with _Window() as w:
        out = arr[1:8, 2:15, 5:30]
    np.testing.assert_array_equal(out, data[1:8, 2:15, 5:30])
    fetched = session.cache_stats()["chunk_fetches"] - fetches0
    n_chunks = 2 * 2 * 2
    assert fetched == n_chunks
    assert w.spans["store.decode"]["n"] == fetched
    assert w.spans["store.decode"]["bytes"] == n_chunks * 4 * 8 * 16 * 4
    assert w.spans["store.get"]["n"] >= 1
    assert w.spans["store.get"]["bytes"] > 0
    # the output buffer, then one copy per chunk
    assert w.spans["store.assemble"]["n"] == 1 + n_chunks
    assert w.spans["store.assemble"]["bytes"] == out.nbytes


# -- the served path -----------------------------------------------------

@pytest.fixture
def served(catalog):
    service = ArchiveService(catalog)
    with ArchiveServer(service) as server:
        yield service, server
    service.close()


@pytest.mark.parametrize("kind", ["qvp", "qpe"])
def test_served_product_dispatches_one_kernel_call(catalog, served, kind):
    service, server = served
    session = service.session("public", "KVNX")
    n_scans = session.array(f"{VCP}/time").shape[0]
    field = session.array(f"{VCP}/sweep_0/DBZH").read()
    # what product code hands the kernel: the field, and for the QPE the
    # float32 integration weight of each scan
    host = field.nbytes + (4 * n_scans if kind == "qpe" else 0)
    with _Window(requests=1) as w:
        status, _body = _get(
            server, f"/products/{kind}?repo=KVNX&vcp={VCP}&sweep=0")
    assert status == 200
    assert w.spans["dispatch.call"]["n"] == 1
    assert w.spans["dispatch.wait"]["n"] == 1
    assert w.spans["dispatch.call"]["bytes"] == host
    assert w.spans["product.compute"]["n"] == 1
    assert w.spans["product.encode"]["n"] == 1
    assert w.spans["http.request"]["n"] == 1
    # the layers inside the computation, timed on its thread, take no
    # more than it does (a multi-chunk read's get, decode and assemble
    # spans may run on the read pool's threads, and sum thread-seconds:
    # ``store.read`` is the read's wall time on this one)
    assert w.spans["store.read"]["n"] >= 1
    inner = sum(w.spans[n]["s"] for n in ("dispatch.call", "dispatch.wait",
                                          "store.read"))
    assert inner <= w.spans["product.compute"]["s"]


def test_server_records_one_queue_wait_per_request(served):
    _service, server = served
    with _Window(requests=5) as w:
        for _ in range(5):
            assert _get(server, "/catalog")[0] == 200
    assert w.spans["http.queue"]["n"] == 5
    assert w.spans["http.request"]["n"] == 5
    assert w.spans["http.queue"]["s"] >= 0.0


def test_stats_route_carries_spans(served):
    _service, server = served
    assert _get(server, f"/products/qpe?repo=KVNX&vcp={VCP}")[0] == 200
    status, body = _get(server, "/stats")
    assert status == 200
    spans = json.loads(body)["spans"]
    for name in ("http.queue", "http.request", "product.compute",
                 "store.decode", "dispatch.call"):
        assert spans[name]["n"] >= 1, name
        assert set(spans[name]) == {"n", "s", "bytes"}


def test_trace_events_nest_and_carry_the_request(served, tmp_path):
    import jax
    from jax.profiler import ProfileData

    _service, server = served
    traced0 = obs.snapshot(traced=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with _Window(requests=1) as w:
            status, _body = _get(server,
                                 f"/products/qvp?repo=KVNX&vcp={VCP}",
                                 headers={"X-Tenant": "acme"})
    finally:
        jax.profiler.stop_trace()
    assert status == 200
    # the table's traced part holds exactly what the session saw
    assert _delta(traced0, obs.snapshot(traced=True)) == w.spans
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    events.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns, ev.duration_ns,
                         dict(ev.stats)))
    (request,) = events["repro.http.request"]
    assert request[3]["route"] == "/products/qvp"
    assert request[3]["tenant"] == "acme"
    assert request[3]["id"] >= 1
    (compute,) = events["repro.product.compute"]
    assert compute[3]["kind"] == "qvp"
    # one thread, and the computation inside the request
    assert compute[0] == request[0]
    assert request[1] <= compute[1]
    assert compute[1] + compute[2] <= request[1] + request[2]
    (call,) = events["repro.dispatch.call"]
    assert call[3]["kernel"] == "qvp_reduce"
    assert "repro.store.decode" in events
