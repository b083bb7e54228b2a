"""Flight recorder (observability/flight.py): ring bounds, tracer/log
capture, atomic dumps, trigger installation, the /debug/flight endpoint,
and the satellite contract that a dump in progress never blocks or
corrupts a concurrent /metrics + /healthz scrape."""

import json
import os
import signal
import sys
import threading
import time
import urllib.request

import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.observability import flight, tracing
from elasticdl_tpu.observability.flight import FlightRecorder
from elasticdl_tpu.observability.http import ObservabilityServer
from elasticdl_tpu.observability.registry import default_registry


@pytest.fixture(autouse=True)
def _fresh_singleton():
    flight.reset_for_tests()
    yield
    flight.reset_for_tests()


def test_ring_is_bounded_and_ordered():
    rec = FlightRecorder(ring=32, role="w")
    for i in range(100):
        rec.record("event", f"e{i}", i=i)
    snap = rec.snapshot()
    assert len(snap) == 32
    # oldest-first, only the newest 32 survive
    assert snap[0]["name"] == "e68" and snap[-1]["name"] == "e99"
    # seqs are monotonic across evictions
    seqs = [r["seq"] for r in snap]
    assert seqs == sorted(seqs) and seqs[-1] == 100


def test_tracer_sink_captures_spans_and_events():
    rec = FlightRecorder(ring=64, role="w").attach_tracing()
    try:
        with tracing.span("rescale.unit_test"):
            tracing.event("unit.event", k=1)
    finally:
        rec.detach_tracing()
    names = [r.get("name") for r in rec.snapshot()]
    assert "rescale.unit_test" in names and "unit.event" in names
    # detach really detaches
    tracing.event("after.detach")
    assert "after.detach" not in [r.get("name") for r in rec.snapshot()]


def test_log_capture_warning_and_up():
    import logging

    rec = FlightRecorder(ring=64, role="w").attach_logging()
    try:
        log = logging.getLogger("elasticdl_tpu.test_flight")
        log.warning("something %s happened", "bad")
        log.debug("noise")
    finally:
        rec.detach_logging()
    logs = [r for r in rec.snapshot() if r["kind"] == "log"]
    assert any("something bad happened" in r["msg"] for r in logs)
    assert not any("noise" in r["msg"] for r in logs)


def test_dump_is_atomic_parseable_and_overwrites(tmp_path):
    rec = FlightRecorder(ring=64, role="worker-3")
    rec.configure(dir=str(tmp_path), job_name="j")
    rec.record("event", "before.crash", x=1)
    path = rec.dump("crash:Boom")
    assert path and os.path.basename(path).startswith("flight-worker-3-")
    bundle = json.load(open(path))
    assert bundle["schema"] == 1 and bundle["reason"] == "crash:Boom"
    assert bundle["role"] == "worker-3" and bundle["meta"]["job_name"] == "j"
    assert any(r.get("name") == "before.crash" for r in bundle["records"])
    assert isinstance(bundle["metrics"], dict)
    # no .tmp litter (atomic replace)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    # second dump overwrites the same file and carries the history
    path2 = rec.dump("sigusr2")
    assert path2 == path
    bundle2 = json.load(open(path))
    assert bundle2["reason"] == "sigusr2"
    assert bundle2["prior_dump_reasons"] == ["crash:Boom"]
    assert bundle2["dump_seq"] == 2


def test_metrics_delta_is_since_last_dump(tmp_path):
    ctr = default_registry().counter(
        "edl_test_flight_delta_total", "test counter")
    rec = FlightRecorder(ring=16, role="w")
    rec.configure(dir=str(tmp_path))
    ctr.inc(3)
    b1 = json.load(open(rec.dump("one")))
    assert b1["metrics_delta"].get("edl_test_flight_delta_total") == 3.0
    b2 = json.load(open(rec.dump("two")))   # nothing moved since dump one
    assert "edl_test_flight_delta_total" not in b2["metrics_delta"]
    ctr.inc(2)
    b3 = json.load(open(rec.dump("three")))
    assert b3["metrics_delta"].get("edl_test_flight_delta_total") == 2.0


def test_dump_without_dir_is_memory_only_and_never_raises():
    rec = FlightRecorder(ring=16, role="w")
    assert rec.dump("whatever") is None
    # an unwritable dir fails the dump quietly, not the process
    rec.configure(dir="/proc/definitely/not/writable")
    assert rec.dump("whatever") is None


def test_fault_crash_hook_runs_before_exit():
    seen = []
    faults.add_crash_hook(lambda site: seen.append(site))
    try:
        faults._run_crash_hooks("worker.heartbeat")
    finally:
        faults._CRASH_HOOKS.clear()
    assert seen == ["worker.heartbeat"]


def test_install_crash_hooks_excepthook_and_sigusr2(tmp_path):
    rec = flight.get_recorder()
    rec.configure(dir=str(tmp_path), role="proc")
    prev_hook = sys.excepthook
    try:
        flight.install_crash_hooks()
        # excepthook: chained wrapper dumps with the exception type
        assert sys.excepthook is not prev_hook
        sys.excepthook(ValueError, ValueError("boom"), None)
        bundle = json.load(open(rec.last_dump_path))
        assert bundle["reason"] == "crash:ValueError"
        assert any(
            r.get("name") == "flight.crash" for r in bundle["records"]
        )
        # SIGUSR2 (the ProcessManager.request_flight_dump trigger): the
        # handler only arms an event — a drainer THREAD dumps, so a signal
        # landing while the main thread holds the tracer/registry locks
        # can never deadlock the worker it targets. Async: poll briefly.
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.time() + 10
        while time.time() < deadline:
            bundle = json.load(open(rec.last_dump_path))
            if bundle["reason"] == "sigusr2":
                break
            time.sleep(0.05)
        assert bundle["reason"] == "sigusr2"
        # fault-injector pre-crash hook is registered
        assert faults._CRASH_HOOKS
        faults._run_crash_hooks("master_crash")
        bundle = json.load(open(rec.last_dump_path))
        assert bundle["reason"] == "fault:master_crash"
    finally:
        sys.excepthook = prev_hook
        faults._CRASH_HOOKS.clear()
        try:
            signal.signal(signal.SIGUSR2, signal.SIG_DFL)
        except ValueError:
            pass


# ---------------------------------------------------------------------- #
# /debug/flight endpoint + the concurrent-scrape satellite


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.status, resp.read()


def test_debug_flight_endpoint_dumps_and_serves(tmp_path):
    rec = FlightRecorder(ring=32, role="worker-9")
    rec.configure(dir=str(tmp_path))
    rec.record("event", "endpoint.test")
    server = ObservabilityServer(role="worker-9", flight=rec)
    port = server.start()
    try:
        status, body = _get(port, "/debug/flight")
        assert status == 200
        bundle = json.loads(body)
        assert bundle["reason"] == "http" and bundle["role"] == "worker-9"
        assert any(
            r.get("name") == "endpoint.test" for r in bundle["records"]
        )
        # the dump also landed on disk, atomically
        assert bundle["dumped_to"] and os.path.exists(bundle["dumped_to"])
    finally:
        server.stop()


def test_scrapes_never_block_or_corrupt_during_dumps(tmp_path):
    """Satellite: /healthz + /metrics under concurrent scrape while flight
    dumps are in progress — every scrape must come back 200 and
    parseable, with no scrape stuck behind a dump's file I/O."""
    rec = FlightRecorder(ring=256, role="worker-1")
    rec.configure(dir=str(tmp_path))
    server = ObservabilityServer(
        role="worker-1", flight=rec, health_fn=lambda: {"extra": 1}
    )
    port = server.start()
    stop = threading.Event()
    errors = []

    def dumper():
        i = 0
        while not stop.is_set():
            rec.record("event", "spin", i=i)
            rec.dump(f"loop:{i}")
            i += 1

    def scraper(path, check):
        try:
            for _ in range(25):
                status, body = _get(port, path)
                assert status == 200
                check(body)
        except Exception as e:           # noqa: BLE001 — collected below
            errors.append((path, repr(e)))

    def check_metrics(body):
        text = body.decode()
        assert "edl_flight_records_total" in text

    def check_healthz(body):
        payload = json.loads(body)
        assert payload["status"] == "ok" and payload["extra"] == 1

    dump_thread = threading.Thread(target=dumper, daemon=True)
    dump_thread.start()
    threads = [
        threading.Thread(target=scraper, args=("/metrics", check_metrics)),
        threading.Thread(target=scraper, args=("/healthz", check_healthz)),
        threading.Thread(target=scraper, args=("/metrics", check_metrics)),
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "scrape stuck behind a dump"
    finally:
        stop.set()
        dump_thread.join(timeout=10)
        server.stop()
    assert not errors, errors
    # and the final bundle on disk is whole (atomic writes throughout)
    final = json.load(open(rec.last_dump_path))
    assert final["kind"] == "flight"
