"""Archive HTTP service: lifecycle, tenancy, caching, coalescing, and
the bitwise server-vs-in-process contract."""

from __future__ import annotations

import http.client
import threading

import numpy as np
import pytest

from repro.catalog import Catalog
from repro.catalog import query as q
from repro.catalog.federation import federated_mosaic
from repro.etl import generate_raw_archive, ingest
from repro.radar.grid import cappi_from_session, column_max_from_session
from repro.radar.qpe import qpe_from_session
from repro.radar.qvp import qvp_from_session
from repro.serve.http import (ApiError, ArchiveServer, ArchiveService,
                              decode_payload, encode_product)
from repro.serve.scheduling import ByteBudgetCache, SingleFlight, plan_batches
from repro.store import ObjectStore, Repository

SITES = ["KVNX", "KTLX"]
VCP = "VCP-212"


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    base = tmp_path_factory.mktemp("serve-http")
    catalog = Catalog.create(str(base / "catalog"))
    repos = {}
    for i, site in enumerate(SITES):
        raw = ObjectStore(str(base / f"raw-{site}"))
        generate_raw_archive(raw, site_id=site, n_scans=3, n_az=24,
                             n_gates=280, n_sweeps=2, seed=11 + i)
        repos[site] = Repository.create(str(base / f"store-{site}"))
        ingest(raw, repos[site], batch_size=3, time_chunk=2,
               catalog=catalog, repo_id=site)
    return catalog, repos


@pytest.fixture(scope="module")
def server(archive):
    catalog, _repos = archive
    service = ArchiveService(catalog)
    with ArchiveServer(service) as srv:
        yield srv
    service.close()


def _get(server, path, headers=None):
    host, port = server.address
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


# -- substrate ---------------------------------------------------------------

def test_plan_batches_shapes():
    assert plan_batches(0) == []
    assert [list(b) for b in plan_batches(5)] == [[0, 1, 2, 3, 4]]
    assert [list(b) for b in plan_batches(5, 2)] == [[0, 1], [2, 3], [4]]
    assert [list(b) for b in plan_batches(4, 9)] == [[0, 1, 2, 3]]
    with pytest.raises(ValueError):
        plan_batches(-1)


def test_single_flight_coalesces_concurrent_calls():
    flight = SingleFlight()
    barrier = threading.Barrier(6)
    calls = []
    results = []

    def work():
        calls.append(1)
        return object()

    def run():
        barrier.wait()
        results.append(flight.do("key", work))

    threads = [threading.Thread(target=run) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = flight.stats()
    assert stats["total"] == 6
    assert stats["computations"] == len(calls)
    assert stats["coalesced"] == 6 - len(calls)
    # every call in one coalescing group got the *same* object
    assert len(results) == 6


def test_single_flight_propagates_errors():
    flight = SingleFlight()
    with pytest.raises(RuntimeError, match="boom"):
        flight.do("k", lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    # the failed flight is retired: a retry computes fresh
    assert flight.do("k", lambda: 7) == 7


def test_byte_budget_cache_evicts_lru():
    cache = ByteBudgetCache(10)
    assert cache.put("a", "A", 4) == []
    assert cache.put("b", "B", 4) == []
    assert cache.get("a") == "A"           # refreshes a
    assert cache.put("c", "C", 4) == [("b", "B")]   # b was LRU
    assert cache.get("b") is None
    stats = cache.stats()
    assert stats["nbytes"] == 8 and stats["entries"] == 2
    assert stats["hits"] == 1 and stats["misses"] == 1
    assert sorted(k for k, _v in cache.pop_all()) == ["a", "c"]
    assert cache.stats()["entries"] == 0


# -- lifecycle ---------------------------------------------------------------

def test_server_starts_and_stops_on_ephemeral_port(archive):
    catalog, _repos = archive
    service = ArchiveService(catalog)
    server = ArchiveServer(service).start()
    try:
        assert server.address[1] > 0
        status, _h, body = _get(server, "/catalog")
        assert status == 200 and b"repositories" in body
    finally:
        server.close()
        service.close()
    server.close()  # idempotent


# -- catalog / query ---------------------------------------------------------

def test_catalog_endpoint_lists_repositories(server):
    status, headers, body = _get(server, "/catalog")
    assert status == 200
    assert headers["Content-Type"] == "application/json"
    import json
    doc = json.loads(body)
    assert sorted(doc["repositories"]) == sorted(SITES)
    assert "qvp" in doc["products"]


def test_query_endpoint_matches_inprocess(archive, server):
    catalog, _repos = archive
    status, _h, body = _get(
        server, "/query?moment=DBZH&value_gt=35.0&refs=1")
    assert status == 200
    import json
    doc = json.loads(body)
    ref = q.query(catalog, q.moment("DBZH"), q.value_gt(35.0))
    assert doc["n_matches"] == ref.n_matches
    assert doc["chunks_read"] == ref.chunks_read
    assert doc["pruning_ratio"] == pytest.approx(ref.pruning_ratio)
    assert any(s["chunk_refs"] for s in doc["scans"])


def test_chunk_endpoint_serves_cas_blobs(archive, server):
    catalog, repos = archive
    import json
    _s, _h, body = _get(server, "/query?moment=DBZH&refs=1")
    scan = next(s for s in json.loads(body)["scans"] if s["chunk_refs"])
    ref = scan["chunk_refs"][0]
    status, headers, blob = _get(server,
                                 f"/chunks/{ref}?repo={scan['repo']}")
    assert status == 200
    assert headers["ETag"] == f'"{ref}"'
    session = repos[scan["repo"]].readonly_session()
    try:
        assert blob == bytes(session.get_blob(ref))
    finally:
        session.close()
    # CAS hash is the strong ETag: revalidation is a 304
    status, _h2, body2 = _get(server, f"/chunks/{ref}?repo={scan['repo']}",
                              headers={"If-None-Match": f'"{ref}"'})
    assert status == 304 and body2 == b""


# -- products: bitwise server-vs-in-process ----------------------------------

def test_product_bodies_bitwise_equal_inprocess(archive, server):
    catalog, repos = archive
    session = repos["KVNX"].readonly_session()
    try:
        expected = {
            "qvp": encode_product(qvp_from_session(
                session, vcp=VCP, sweep=0, moment="DBZH",
                quality_moment=None)),
            "qpe": encode_product(qpe_from_session(
                session, vcp=VCP, sweep=0, moment="DBZH")),
            "cappi": encode_product(cappi_from_session(
                session, vcp=VCP, moment="DBZH", altitude_m=2000.0,
                ny=40, nx=40)),
            "column_max": encode_product(column_max_from_session(
                session, vcp=VCP, moment="DBZH", ny=40, nx=40)),
        }
    finally:
        session.close()
    expected["mosaic"] = encode_product(federated_mosaic(
        catalog, moment="DBZH", product="column_max", ny=40, nx=40))

    paths = {
        "qvp": f"/products/qvp?repo=KVNX&vcp={VCP}&sweep=0",
        "qpe": f"/products/qpe?repo=KVNX&vcp={VCP}&sweep=0",
        "cappi": f"/products/cappi?repo=KVNX&vcp={VCP}&ny=40&nx=40",
        "column_max":
            f"/products/column_max?repo=KVNX&vcp={VCP}&ny=40&nx=40",
        "mosaic": "/products/mosaic?ny=40&nx=40",
    }
    for kind, path in paths.items():
        status, headers, body = _get(server, path)
        assert status == 200, (kind, body)
        assert body == expected[kind], (
            f"{kind}: served body != in-process encoding")
        assert headers["ETag"].strip('"')
        # decodable round-trip
        doc, arrays = decode_payload(body)
        assert arrays, kind


def test_product_etag_304_roundtrip(server):
    path = f"/products/qvp?repo=KVNX&vcp={VCP}&sweep=0"
    _s, headers, body = _get(server, path)
    etag = headers["ETag"]
    status, h304, body304 = _get(server, path,
                                 headers={"If-None-Match": etag})
    assert status == 304 and body304 == b""
    assert h304["ETag"] == etag
    # a weak validator of the same hash also matches
    status, _h, _b = _get(server, path,
                          headers={"If-None-Match": f"W/{etag}"})
    assert status == 304


# -- coalescing --------------------------------------------------------------

def test_concurrent_identical_requests_compute_once(archive):
    catalog, _repos = archive
    service = ArchiveService(catalog)
    n = 8
    path = f"/products/column_max?repo=KTLX&vcp={VCP}&ny=32&nx=32"
    with ArchiveServer(service, workers=n) as srv:
        barrier = threading.Barrier(n)
        bodies = [None] * n

        def hit(i):
            barrier.wait()
            status, _h, body = _get(srv, path)
            assert status == 200
            bodies[i] = body

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        assert all(b == bodies[0] for b in bodies), \
            "coalesced responses must be bitwise-identical"
        stats = service.stats()
        # one unique request: exactly one computation, regardless of
        # how the n concurrent calls split between coalesce and cache
        assert stats["product_flight"]["computations"] == 1
        total = stats["product_flight"]["total"]
        hits = stats["product_cache"]["hits"]
        assert total + hits == n
        # and a repeat is served without a new computation
        _s, _h, again = _get(srv, path)
        assert again == bodies[0]
        assert service.stats()["product_flight"]["computations"] == 1
    service.close()


# -- tenancy -----------------------------------------------------------------

def test_tenants_get_isolated_session_caches(archive):
    catalog, _repos = archive
    service = ArchiveService(catalog)
    try:
        sa = service.session("tenant-a", "KVNX")
        sb = service.session("tenant-b", "KVNX")
        assert sa is not sb, "tenants must not share sessions"
        assert service.session("tenant-a", "KVNX") is sa, \
            "same tenant re-uses its cached session"
        stats = service.stats()["tenants"]
        assert stats["tenant-a"]["entries"] == 1
        assert stats["tenant-b"]["entries"] == 1
    finally:
        service.close()


def test_tenant_header_routes_to_own_cache(archive, server):
    for tenant in ("acme", "umbrella"):
        status, _h, _b = _get(server, "/catalog",
                              headers={"X-Tenant": tenant})
        assert status == 200
        # /query always runs on the tenant's own cached sessions
        # (products may be served from the shared body cache)
        status, _h, _b = _get(server, "/query?moment=DBZH",
                              headers={"X-Tenant": tenant})
        assert status == 200
    import json
    _s, _h, body = _get(server, "/stats")
    tenants = json.loads(body)["tenants"]
    assert "acme" in tenants and "umbrella" in tenants


def test_session_budget_evicts_lru_session(archive):
    catalog, _repos = archive
    service = ArchiveService(catalog, sessions_per_tenant=1)
    try:
        sa = service.session("t", "KVNX")
        service.session("t", "KTLX")       # evicts (and closes) sa
        assert service.stats()["tenants"]["t"]["entries"] == 1
        assert service.session("t", "KVNX") is not sa
    finally:
        service.close()


# -- malformed requests ------------------------------------------------------

@pytest.mark.parametrize("path,frag", [
    ("/products/qvp", "missing required parameter"),
    ("/products/qvp?repo=KVNX", "missing required parameter"),
    (f"/products/qvp?repo=KVNX&vcp={VCP}&sweep=abc", "bad value"),
    (f"/products/qvp?repo=KVNX&vcp={VCP}&i0=0", "given together"),
    ("/query?time0=1.0", "given together"),
    ("/query?bbox=1,2,3", "bbox"),
    ("/query?prune=maybe", "bad value"),
    ("/query?sweep=0&sweep=1", "duplicate parameter"),
    (f"/products/mosaic?product=ppi", "column_max or cappi"),
])
def test_bad_request_is_400_with_message(server, path, frag):
    status, _h, body = _get(server, path)
    assert status == 400, (path, body)
    assert frag.encode() in body


@pytest.mark.parametrize("path", [
    "/nope",
    "/products/sounding?repo=KVNX",
    "/products/qvp?repo=NOPE&vcp=VCP-212",
    "/chunks/deadbeef?repo=KVNX",
])
def test_unknown_things_are_404(server, path):
    status, _h, body = _get(server, path)
    assert status == 404, (path, body)
    assert b"error" in body


@pytest.mark.parametrize("path", [
    f"/products/qvp?repo=KVNX&vcp={VCP}&sweep=9",
    f"/products/qpe?repo=KVNX&vcp={VCP}&moment=NOPE",
    "/products/cappi?repo=KVNX&vcp=VCP-999",
])
def test_missing_product_inputs_are_404(server, path):
    status, _h, body = _get(server, path)
    assert status == 404, (path, body)
    assert b"product inputs not found" in body


def test_kernel_failure_is_500_not_404(archive, monkeypatch):
    """A failure inside the product computation (a kernel that does not
    compile, a device out of memory) is the server's fault: it must not
    reach the client disguised as a missing input."""
    import repro.serve.http as http_mod

    def broken(*_a, **_k):
        raise ValueError("Mosaic failed to compile the kernel")

    monkeypatch.setattr(http_mod, "compute_product", broken)
    catalog, _repos = archive
    service = ArchiveService(catalog)
    try:
        with ArchiveServer(service) as srv:
            status, _h, body = _get(
                srv, f"/products/qvp?repo=KVNX&vcp={VCP}&sweep=0")
    finally:
        service.close()
    assert status == 500, body
    assert b"Mosaic failed to compile" in body


def test_bad_tenant_is_400(server):
    status, _h, body = _get(server, "/catalog",
                            headers={"X-Tenant": "bad tenant!"})
    assert status == 400
    assert b"tenant" in body


def test_missing_chunk_repo_param_is_400(server):
    status, _h, _b = _get(server, "/chunks/abc123")
    assert status == 400


def test_api_error_shape():
    err = ApiError(418, "teapot")
    assert err.status == 418 and err.message == "teapot"
