"""Journal group-commit saturation (ISSUE 16): the open-batch queue
depth is observable and bounded by the window swap, the backpressure
warning edge-triggers once per saturated window, a stuck committer can
NEVER silently ack (Commit.wait raises JournalCommitError on timeout or
flush error), and an N-thread x M-commit burst lands every record —
replaying to the identical state twice."""

import contextlib
import dataclasses
import logging
import threading

import pytest

from elasticdl_tpu.master.journal import (
    Commit,
    ControlPlaneJournal,
    JournalCommitError,
    replay_lines,
)


@contextlib.contextmanager
def capture_journal_warnings():
    """The package logger is configured propagate=False (log_utils), so
    caplog's root handler never sees journal records — attach a list
    handler to the journal logger itself."""
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = logging.getLogger("elasticdl_tpu.master.journal")
    handler = _Capture(level=logging.WARNING)
    prior_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.WARNING)
    try:
        yield records
    finally:
        logger.removeHandler(handler)
        logger.setLevel(prior_level)


def _task(task_id):
    return {"task_id": task_id, "type": 0, "shard_name": "s",
            "start": 0, "end": 10, "epoch": 0, "retries": 0}


# ---------------------------------------------------------------------- #
# queue depth / high water / backpressure


def test_commit_queue_high_water_tracks_the_burst(tmp_path):
    # a wide window so the whole burst lands in ONE open batch: the
    # high-water mark must see every queued record, and the swap must
    # reset the live depth for the next window
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=200.0)
    try:
        commits = [j.append("task_create", task=_task(i), front=False)
                   for i in range(64)]
        for c in commits:
            c.wait()
        assert 1 <= j.commit_queue_high_water <= 64
        # the mark is a max, not a live gauge: it survives the flush
        j.append("epoch_advance", epoch=1).wait()
        assert j.commit_queue_high_water >= 1
    finally:
        j.close()


def test_backpressure_warning_edge_triggers_once_per_window(tmp_path):
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=200.0)
    # shrink the warn threshold (instance attr shadows the class
    # default) so a unit-sized burst crosses it many times over
    j.COMMIT_QUEUE_WARN_DEPTH = 8
    try:
        with capture_journal_warnings() as records:
            commits = [j.append("task_create", task=_task(i), front=False)
                       for i in range(32)]
            for c in commits:
                c.wait()
        warnings = [r for r in records
                    if "BACKPRESSURE" in r.getMessage()]
        assert len(warnings) == 1      # edge-triggered, not 24 repeats
        assert j.commit_queue_high_water > j.COMMIT_QUEUE_WARN_DEPTH
    finally:
        j.close()


def test_no_backpressure_warning_below_threshold(tmp_path):
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=50.0)
    try:
        with capture_journal_warnings() as records:
            for i in range(16):
                j.append("task_create", task=_task(i), front=False).wait()
        assert not [r for r in records
                    if "BACKPRESSURE" in r.getMessage()]
    finally:
        j.close()


# ---------------------------------------------------------------------- #
# the never-silent-ack contract


def test_commit_wait_timeout_raises_not_acks():
    # a commit whose event never fires (committer stuck / disk stalled):
    # the caller must get JournalCommitError, never a clean return it
    # could mistake for durability
    stuck = Commit(threading.Event(), batch=None)
    with pytest.raises(JournalCommitError, match="not durable"):
        stuck.wait(timeout_s=0.05)


def test_commit_wait_surfaces_flush_errors():
    class _Batch:
        error = OSError("disk on fire")

    done = threading.Event()
    done.set()
    failed = Commit(done, batch=_Batch())
    with pytest.raises(JournalCommitError, match="group commit failed"):
        failed.wait(timeout_s=0.05)


def test_append_after_close_is_loudly_non_durable(tmp_path):
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=5.0)
    j.close()
    with capture_journal_warnings() as records:
        c = j.append("epoch_advance", epoch=1)
    # the no-op handle resolves (callers can't deadlock on shutdown)
    # but the drop is logged — not a silent ack into the void
    c.wait(timeout_s=0.05)
    assert any("dropped" in r.getMessage() for r in records)


# ---------------------------------------------------------------------- #
# concurrent burst: every record lands, replay is deterministic


@pytest.mark.parametrize("threads,commits", [(8, 50)])
def test_threaded_burst_replays_record_identical(tmp_path, threads,
                                                 commits):
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=2.0)
    errors = []

    def worker(base):
        try:
            handles = [
                j.append("task_create", task=_task(base + i), front=False)
                for i in range(commits)
            ]
            for h in handles:
                h.wait()
        except Exception as e:          # pragma: no cover - failure path
            errors.append(e)

    ts = [threading.Thread(target=worker, args=(t * commits,))
          for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    j.close()
    assert not errors

    path = j.path
    with open(path, encoding="utf-8") as f:
        lines = f.readlines()
    a = replay_lines(lines)
    b = replay_lines(lines)
    assert a.dropped_lines == 0
    assert a.records == 1 + threads * commits          # header + burst
    assert a.dispatcher is not None
    assert len(a.dispatcher.todo) == threads * commits
    assert dataclasses.asdict(a.dispatcher) \
        == dataclasses.asdict(b.dispatcher)
    # every acked task_id is present exactly once — group-commit
    # batching must not coalesce, drop, or duplicate under contention
    ids = sorted(t["task_id"] for t in a.dispatcher.todo)
    assert ids == list(range(threads * commits))
