"""Control-plane journal (master/journal.py): append/replay round-trips,
generation bumps, torn-tail tolerance, atomic rotation, and the dispatcher/
membership restore paths a crashed master's successor runs through."""

import json
import os

from elasticdl_tpu.common import membership_signal
from elasticdl_tpu.master.journal import (
    ControlPlaneJournal,
    replay_lines,
)
from elasticdl_tpu.master.membership import Membership
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb


def read_journal(ckpt_dir):
    path = os.path.join(ckpt_dir, "control", "journal.jsonl")
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------- #
# raw journal mechanics


def test_fresh_journal_writes_header_generation_1(tmp_path):
    j = ControlPlaneJournal(str(tmp_path))
    assert j.generation == 1 and not j.recovered
    recs = read_journal(str(tmp_path))
    assert recs[0] == {"t": "header", "v": 1, "generation": 1}
    j.close()


def test_reopen_bumps_generation_and_compacts(tmp_path):
    j1 = ControlPlaneJournal(str(tmp_path))
    j1.append("epoch_advance", epoch=0)
    j1.append(
        "task_create",
        task={"task_id": 1, "type": 0, "shard_name": "s", "start": 0,
              "end": 10, "epoch": 0, "retries": 0},
        front=False,
    )
    j1.close()

    j2 = ControlPlaneJournal(str(tmp_path))
    assert j2.recovered and j2.generation == 2
    snap = j2.dispatcher_snapshot()
    assert snap is not None
    assert snap.epoch == 0 and [t["task_id"] for t in snap.todo] == [1]
    # atomic rotation: the live file is now header + one compacted snapshot
    recs = read_journal(str(tmp_path))
    assert [r["t"] for r in recs] == ["header", "snapshot"]
    assert recs[0]["generation"] == 2

    # and a third boot replays the SNAPSHOT to the same state
    j2.close()
    j3 = ControlPlaneJournal(str(tmp_path))
    assert j3.generation == 3
    assert [t["task_id"] for t in j3.dispatcher_snapshot().todo] == [1]
    j3.close()


def test_inflight_leases_requeued_front_in_lease_order(tmp_path):
    j = ControlPlaneJournal(str(tmp_path))
    for tid in (1, 2, 3):
        j.append(
            "task_create",
            task={"task_id": tid, "type": 0, "shard_name": "s",
                  "start": tid * 10, "end": tid * 10 + 10, "epoch": 0,
                  "retries": 0},
            front=False,
        )
    j.append("task_lease", task_id=2, worker_id=0)
    j.append("task_lease", task_id=1, worker_id=0)
    j.close()

    j2 = ControlPlaneJournal(str(tmp_path))
    snap = j2.dispatcher_snapshot()
    # both in-flight leases conservatively requeued at the FRONT, in lease
    # order, ahead of the never-leased task 3
    assert [t["task_id"] for t in snap.todo] == [2, 1, 3]
    assert snap.requeued_leases == 2
    j2.close()


def test_replayed_lease_after_requeue_not_duplicated():
    # a task leased, requeued (timeout/failure), and RE-leased before the
    # crash appears twice in lease order but must come back exactly once —
    # a duplicate would double-train its records after recovery
    task = {"task_id": 5, "type": 0, "shard_name": "s", "start": 0,
            "end": 10, "epoch": 0, "retries": 0}
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 1}),
        json.dumps({"t": "task_create", "task": task, "front": False}),
        json.dumps({"t": "task_lease", "task_id": 5, "worker_id": 0}),
        json.dumps({"t": "task_requeue", "task_id": 5, "start": 0,
                    "retries": 1}),
        json.dumps({"t": "task_lease", "task_id": 5, "worker_id": 0}),
    ]
    snap = replay_lines(lines).dispatcher
    assert [t["task_id"] for t in snap.todo] == [5]
    assert snap.requeued_leases == 1


def test_replay_stop_training_drops_inflight_training_lease():
    # stop_training condemned all training work; replay must not resurrect
    # a TRAINING lease that was in flight at the stop — but a non-training
    # in-flight lease (prediction) still comes back
    train = {"task_id": 1, "type": 0, "shard_name": "s", "start": 0,
             "end": 10, "epoch": 0, "retries": 0}
    pred = {"task_id": 2, "type": 2, "shard_name": "p", "start": 0,
            "end": 10, "epoch": 0, "retries": 0}
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 1}),
        json.dumps({"t": "task_create", "task": train, "front": False}),
        json.dumps({"t": "task_create", "task": pred, "front": False}),
        json.dumps({"t": "task_lease", "task_id": 1, "worker_id": 0}),
        json.dumps({"t": "task_lease", "task_id": 2, "worker_id": 0}),
        json.dumps({"t": "stop_training", "num_epochs": 1}),
    ]
    snap = replay_lines(lines).dispatcher
    assert snap.stop_training
    assert [t["task_id"] for t in snap.todo] == [2]


def test_replay_drops_evaluation_tasks():
    # EvaluationService state (job ids, metric aggregation) is volatile:
    # a replayed eval task would report into a dead eval job id — or a
    # post-recovery job that reused it. Queued AND in-flight eval tasks
    # are dropped; the successor's re-fired epoch-end trigger recreates
    # the eval job fresh.
    train = {"task_id": 1, "type": 0, "shard_name": "s", "start": 0,
             "end": 10, "epoch": 0, "retries": 0}
    ev_q = {"task_id": 2, "type": 1, "shard_name": "e", "start": 0,
            "end": 10, "epoch": 0, "retries": 0, "eval_job_id": 0}
    ev_fly = {"task_id": 3, "type": 1, "shard_name": "e", "start": 10,
              "end": 20, "epoch": 0, "retries": 0, "eval_job_id": 0}
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 1}),
        json.dumps({"t": "task_create", "task": train, "front": False}),
        json.dumps({"t": "task_create", "task": ev_q, "front": False}),
        json.dumps({"t": "task_create", "task": ev_fly, "front": False}),
        json.dumps({"t": "task_lease", "task_id": 3, "worker_id": 0}),
    ]
    snap = replay_lines(lines).dispatcher
    assert [t["task_id"] for t in snap.todo] == [1]
    assert snap.requeued_leases == 0


def test_batch_commit_is_one_line_and_torn_batch_drops_whole(tmp_path):
    """A multi-record commit rides ONE journal line (append_many): a crash
    mid-write can tear the line, but then the WHOLE batch is dropped at
    replay — never a parseable prefix (an epoch_advance with only some of
    its task creations would replay a partial epoch as if complete)."""
    j = ControlPlaneJournal(str(tmp_path))
    task = {"task_id": 1, "type": 0, "shard_name": "s", "start": 0,
            "end": 10, "epoch": 0, "retries": 0}
    j.append_many([
        ("epoch_advance", {"epoch": 0}),
        ("task_create", {"task": task, "front": False}),
        ("task_create", {"task": dict(task, task_id=2, start=10, end=20),
                         "front": False}),
    ])
    j.close()
    path = os.path.join(str(tmp_path), "control", "journal.jsonl")
    lines = open(path, encoding="utf-8").read().splitlines()
    assert len(lines) == 2                 # header + ONE batch line
    # a torn batch (crash mid-write) loses the whole commit, not a prefix
    torn = lines[0] + "\n" + lines[1][: len(lines[1]) // 2]
    res = replay_lines(torn.splitlines())
    assert res.dropped_lines == 1
    assert res.dispatcher is None          # no partial epoch replayed
    # and the intact batch replays whole
    res = replay_lines(lines)
    assert res.dispatcher.epoch == 0
    assert [t["task_id"] for t in res.dispatcher.todo] == [1, 2]


def test_torn_tail_dropped_not_fatal(tmp_path):
    j = ControlPlaneJournal(str(tmp_path))
    j.append("epoch_advance", epoch=4)
    j.close()
    path = os.path.join(str(tmp_path), "control", "journal.jsonl")
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"t": "task_crea')          # crash mid-append

    j2 = ControlPlaneJournal(str(tmp_path))
    assert j2.recovered and j2.generation == 2
    assert j2.replay.dropped_lines == 1
    assert j2.dispatcher_snapshot().epoch == 4
    j2.close()


def test_append_after_close_is_dropped(tmp_path):
    j = ControlPlaneJournal(str(tmp_path))
    j.close()
    j.append("epoch_advance", epoch=1)       # must not raise or corrupt
    j2 = ControlPlaneJournal(str(tmp_path))
    assert j2.dispatcher_snapshot() is None
    j2.close()


def test_world_version_and_membership_replay():
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 3}),
        json.dumps({"t": "member_join", "worker_id": 0, "name": "a",
                    "version": 1}),
        json.dumps({"t": "member_join", "worker_id": 1, "name": "b",
                    "version": 2}),
        json.dumps({"t": "member_death", "worker_id": 1, "version": 3}),
        json.dumps({"t": "world_version", "version": 7}),
    ]
    res = replay_lines(lines)
    assert res.prior_generation == 3
    assert res.world_version == 7
    ms = res.membership
    by_id = {w["worker_id"]: w for w in ms.workers}
    assert by_id[0]["alive"] and not by_id[1]["alive"]
    assert ms.version == 3 and ms.next_id == 2


def test_journal_type_constants_pinned_to_proto_enum():
    # journal.py avoids importing protobuf; these must track the enum
    from elasticdl_tpu.master import journal as jmod

    assert jmod._TRAINING_TYPE == pb.TRAINING
    assert jmod._EVALUATION_TYPE == pb.EVALUATION
    assert jmod._SAVE_MODEL_TYPE == pb.SAVE_MODEL


# ---------------------------------------------------------------------- #
# component restore round-trips (the successor master's boot path)


def make_dispatcher(journal, **kw):
    kw.setdefault("training_shards", [("s0", 0, 40)])
    kw.setdefault("records_per_task", 10)
    kw.setdefault("shuffle", False)
    kw.setdefault("task_timeout_s", 1e9)
    return TaskDispatcher(journal=journal, **kw)


def test_dispatcher_crash_restore_round_trip(tmp_path):
    j1 = ControlPlaneJournal(str(tmp_path))
    d1 = make_dispatcher(j1)
    t_done = d1.get(0)
    assert d1.report(t_done.task_id, 0, success=True)
    t_inflight = d1.get(0)                    # leased, never reported
    assert t_inflight is not None
    counts_before = d1.counts()
    assert counts_before["finished_training"] == 1
    assert counts_before["doing"] == 1
    j1.close()                                # the crash

    j2 = ControlPlaneJournal(str(tmp_path))
    d2 = make_dispatcher(j2)
    counts = d2.counts()
    assert counts["finished_training"] == 1
    assert counts["doing"] == 0               # lease conservatively requeued
    assert counts["todo"] == 3                # 4 tasks - 1 finished
    # the requeued in-flight lease is re-leased FIRST and re-runs whole
    t_again = d2.get(0)
    assert (t_again.shard_name, t_again.start, t_again.end) == (
        t_inflight.shard_name, t_inflight.start, t_inflight.end
    )
    # drive the job to completion under the new generation
    while True:
        t = d2.get(0)
        if t is None and d2.finished():
            break
        if t is None:
            break
        assert d2.report(t.task_id, 0, success=True)
    assert d2.report(t_again.task_id, 0, success=True)
    assert d2.finished()
    assert d2.counts()["finished_training"] == 4
    j2.close()


def test_dispatcher_restore_preserves_save_model_and_epoch_state(tmp_path):
    j1 = ControlPlaneJournal(str(tmp_path))
    d1 = make_dispatcher(j1, final_save_model=True, num_epochs=1)
    while True:
        t = d1.get(0)
        if t is None or t.type == pb.SAVE_MODEL:
            break
        d1.report(t.task_id, 0, success=True)
    # crashed with the final SAVE_MODEL task leased
    assert t is not None and t.type == pb.SAVE_MODEL
    j1.close()

    j2 = ControlPlaneJournal(str(tmp_path))
    d2 = make_dispatcher(j2, final_save_model=True, num_epochs=1)
    t2 = d2.get(0)
    # replay knew save_model was already created: the requeued one is
    # re-leased, not duplicated
    assert t2.type == pb.SAVE_MODEL
    assert d2.counts()["todo"] == 0
    d2.report(t2.task_id, 0, success=True)
    assert d2.finished()
    j2.close()


def test_restore_refires_epoch_end_callbacks_at_least_once(tmp_path):
    """epoch_end is journaled inside the lock but its callbacks (the eval
    trigger) run AFTER it, outside — a crash in between must not skip the
    final evaluation forever. Restore re-derives the terminal flags, so
    the successor re-fires epoch-end at-least-once."""
    j1 = ControlPlaneJournal(str(tmp_path))
    d1 = make_dispatcher(j1, num_epochs=1)
    while True:
        t = d1.get(0)
        if t is None:
            break
        assert d1.report(t.task_id, 0, success=True)
    # epoch_end + training_done + job_end are all journaled by now; the
    # crash window under test is "flag durable, callback not yet run"
    j1.close()

    j2 = ControlPlaneJournal(str(tmp_path))
    fired = []
    d2 = make_dispatcher(j2, num_epochs=1)
    d2.add_epoch_end_callback(fired.append)
    d2.poke()
    assert fired == [0]                    # re-fired for the final epoch
    d2.poke()                              # job-end defers one pass behind
    assert d2.finished()
    j2.close()


def test_membership_crash_restore_and_revival(tmp_path):
    j1 = ControlPlaneJournal(str(tmp_path))
    m1 = Membership(heartbeat_timeout_s=1e9, journal=j1)
    w0 = m1.register("alpha")
    w1 = m1.register("beta")
    m1.mark_dead(w1.worker_id, reason="test")
    v_before = m1.version
    j1.close()

    j2 = ControlPlaneJournal(str(tmp_path))
    m2 = Membership(heartbeat_timeout_s=1e9, journal=j2)
    assert m2.version == v_before
    assert m2.alive_count() == 1
    # live worker's reconnect is idempotent: same id, NO version bump
    info = m2.reregister(w0.worker_id, "alpha")
    assert info.worker_id == w0.worker_id and m2.version == v_before
    # a worker reaped during the outage is revived — that IS a change
    revived = m2.reregister(w1.worker_id, "beta")
    assert revived.worker_id == w1.worker_id and revived.alive
    assert m2.version == v_before + 1
    assert m2.alive_count() == 2
    # fresh ids keep advancing past replayed ones (no id reuse)
    w2 = m2.register("gamma")
    assert w2.worker_id == 2
    j2.close()


def test_epoch_advance_commits_with_its_task_batch(tmp_path, monkeypatch):
    """epoch_advance and its task creations land in ONE append_many commit
    (one fsync): a crash between a lone epoch_advance and the batch would
    replay an epoch with an empty todo, and the successor would fire
    epoch_end over zero tasks and skip the epoch's data entirely."""
    j = ControlPlaneJournal(str(tmp_path))
    commits = []
    orig = j.append_many

    def recording(records):
        commits.append([rtype for rtype, _ in records])
        return orig(records)

    monkeypatch.setattr(j, "append_many", recording)
    make_dispatcher(j)                     # 40 records / 10 per task
    assert commits == [["epoch_advance"] + ["task_create"] * 4]
    j.close()


def test_discard_retires_journal_so_resubmit_starts_fresh(tmp_path):
    # Master.shutdown discards the journal after a FINISHED job: a live
    # journal replaying job_end/training_done would make a re-submission
    # with the same checkpoint_dir come up born-finished and no-op
    j1 = ControlPlaneJournal(str(tmp_path))
    d1 = make_dispatcher(j1)
    while True:
        t = d1.get(0)
        if t is None:
            break
        assert d1.report(t.task_id, 0, success=True)
    assert d1.finished()
    j1.discard()
    assert not os.path.exists(j1.path)
    # ... but the final state survives for forensics
    assert os.path.exists(j1.path + ".completed")

    j2 = ControlPlaneJournal(str(tmp_path))
    assert not j2.recovered and j2.generation == 1
    d2 = make_dispatcher(j2)
    assert not d2.finished()
    assert d2.get(0) is not None
    j2.close()


# ---------------------------------------------------------------------- #
# group-commit crash consistency (ISSUE 8)


def drive_schedule(journal):
    """One fixed dispatcher schedule (the replay-identity probe): lease,
    finish, lease+requeue, lease — leaves one in-flight lease behind."""
    d = make_dispatcher(journal)
    t1 = d.get(0)
    assert d.report(t1.task_id, 0, success=True)
    t2 = d.get(0)
    assert d.report(t2.task_id, 0, success=False, err="boom")   # requeue
    t3 = d.get(0)                       # in-flight at "crash" time
    assert t3 is not None
    return d


def flatten_records(lines):
    """Journal lines -> the flat record sequence (batch lines unwrapped),
    headers dropped — the unit 'record-identical' compares in."""
    out = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        rec = json.loads(line)
        if rec.get("t") == "batch":
            out.extend(rec["records"])
        elif rec.get("t") != "header":
            out.append(rec)
    return out


def test_group_commit_replay_record_identical_to_per_commit(tmp_path):
    """The same mutation schedule journaled in per-commit and group-commit
    mode must leave RECORD-IDENTICAL journals (group mode only changes how
    records are packed into lines/fsyncs, never which records exist or
    their order) — and therefore identical crash replays."""
    per_dir, grp_dir = str(tmp_path / "per"), str(tmp_path / "grp")
    j_per = ControlPlaneJournal(per_dir)
    drive_schedule(j_per)
    j_per.close()
    j_grp = ControlPlaneJournal(grp_dir, group_commit_ms=10.0)
    drive_schedule(j_grp)
    j_grp.close()

    def lines(d):
        path = os.path.join(d, "control", "journal.jsonl")
        return open(path, encoding="utf-8").read().splitlines()

    assert flatten_records(lines(per_dir)) == flatten_records(lines(grp_dir))
    # and the replays agree exactly (incl. the conservative lease requeue)
    r_per, r_grp = replay_lines(lines(per_dir)), replay_lines(lines(grp_dir))
    assert r_per.dispatcher == r_grp.dispatcher


def test_torn_group_batch_drops_whole(tmp_path):
    """A group flush rides ONE batch line: tearing it (crash mid-write)
    must drop every commit of that window together — a parseable prefix
    would replay some of a window's commits and not others, an ordering
    no per-commit run could produce."""
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=50.0)
    commits = [
        j.append("epoch_advance", epoch=0),
        j.append("task_create",
                 task={"task_id": 1, "type": 0, "shard_name": "s",
                       "start": 0, "end": 10, "epoch": 0, "retries": 0},
                 front=False),
        j.append("task_lease", task_id=1, worker_id=0),
    ]
    for c in commits:
        c.wait()
    j.close()
    path = os.path.join(str(tmp_path), "control", "journal.jsonl")
    lines = open(path, encoding="utf-8").read().splitlines()
    assert len(lines) == 2                 # header + ONE group batch line
    torn = [lines[0], lines[1][: len(lines[1]) // 2]]
    res = replay_lines(torn)
    assert res.dropped_lines == 1
    assert res.dispatcher is None          # the whole window dropped
    res = replay_lines(lines)
    assert res.dispatcher.epoch == 0       # intact window replays whole


def test_acked_lease_survives_kill_between_ack_and_queue_drain(tmp_path):
    """THE ack-after-fsync guarantee: once get() returned (the ack a
    worker acts on), a kill — even with LATER records still queued and
    unflushed — must replay the lease. The abort drops only the queued
    suffix nobody was told about."""
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=25.0)
    d = make_dispatcher(j)
    task = d.get(0)                        # returns only after the fsync
    assert task is not None
    # a later transition enqueued but NOT awaited: the crash may lose it
    unacked = j.append("world_version", version=9)
    j.abort()                              # SIGKILL semantics
    import pytest

    from elasticdl_tpu.master.journal import JournalCommitError
    with pytest.raises(JournalCommitError):
        unacked.wait(timeout_s=1)

    j2 = ControlPlaneJournal(str(tmp_path))
    snap = j2.dispatcher_snapshot()
    # the acked lease is there — conservatively requeued at the front
    assert snap.requeued_leases == 1
    assert snap.todo[0]["task_id"] == task.task_id
    # the unacked suffix is gone (and that is fine: no one saw its ack)
    assert j2.world_version == 0
    j2.close()


def test_group_commit_flush_coalesces_concurrent_commits(tmp_path):
    """Commits enqueued within one window land in ONE fsync (the
    throughput mechanism) and every waiter is released by it."""
    import threading

    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=30.0)
    results = []

    def mutate(i):
        c = j.append("world_version", version=i)
        c.wait()
        results.append(i)

    threads = [threading.Thread(target=mutate, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert sorted(results) == list(range(8))
    j.close()
    path = os.path.join(str(tmp_path), "control", "journal.jsonl")
    lines = open(path, encoding="utf-8").read().splitlines()
    # 8 commits, far fewer lines than commits (coalesced windows); replay
    # sees the max version regardless of packing
    assert len(lines) < 9
    assert replay_lines(lines).world_version == 7


def test_flush_failure_poisons_journal_no_ack_after_lost_window(
    tmp_path, monkeypatch
):
    """A failed group flush POISONS the journal: a later window's
    successful fsync must not release acks while an earlier window's
    records are lost (flush order == ack-validity order), and writing
    past a possibly-torn tail would fuse lines at replay. Every commit
    after the failure fails its wait()."""
    import pytest

    from elasticdl_tpu.master.journal import JournalCommitError

    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=10.0)
    j.append("epoch_advance", epoch=0).wait()     # healthy window

    real_fsync = os.fsync
    broken = {"on": True}

    def flaky_fsync(fd):
        if broken["on"]:
            raise OSError(28, "No space left on device")
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", flaky_fsync)
    with pytest.raises(JournalCommitError):
        j.append("task_lease", task_id=1, worker_id=0).wait()
    # the disk "recovers" — but the journal must stay poisoned: a commit
    # ordered after the lost window can never validly ack
    broken["on"] = False
    with pytest.raises(JournalCommitError):
        j.append("epoch_advance", epoch=1).wait()
    monkeypatch.setattr(os, "fsync", real_fsync)
    j.abort()
    # replay sees only what was durable BEFORE the failure
    j2 = ControlPlaneJournal(str(tmp_path))
    assert j2.dispatcher_snapshot().epoch == 0
    assert j2.replay.dropped_lines <= 1          # a torn tail at most
    j2.close()


def test_close_racing_window_writes_no_empty_batch_line(tmp_path):
    """close()/abort() racing the committer's window wait must not flush
    a freshly swapped EMPTY batch (a spurious `{"t":"batch","records":[]}`
    line + a zero-record flush in the metrics)."""
    for _ in range(5):                 # the race needs a few attempts
        j = ControlPlaneJournal(str(tmp_path), group_commit_ms=40.0)
        j.append("epoch_advance", epoch=0)      # opens a window
        j.close()                               # races the window wait
        lines = open(j.path, encoding="utf-8").read().splitlines()
        for line in lines:
            rec = json.loads(line)
            if rec.get("t") == "batch":
                assert rec["records"], lines
        assert replay_lines(lines).dispatcher.epoch == 0
        os.remove(j.path)


def test_member_join_replay_carries_led_by():
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 1}),
        json.dumps({"t": "member_join", "worker_id": 0, "name": "leader",
                    "version": 1}),
        json.dumps({"t": "member_join", "worker_id": 1, "name": "leader#p1",
                    "version": 1, "led_by": 0}),
    ]
    ms = replay_lines(lines).membership
    by_id = {w["worker_id"]: w for w in ms.workers}
    assert by_id[0]["led_by"] is None
    assert by_id[1]["led_by"] == 0


# ---------------------------------------------------------------------- #
# membership-signal takeover hygiene (satellite)


def test_clear_stale_on_takeover(tmp_path):
    path = str(tmp_path / "membership_signal.json")
    membership_signal.write_signal(
        path, world_size=4, pending_size=6, world_version=3,
        trace_id="dead-master-reform", master_generation=1,
    )
    assert membership_signal.clear_stale_on_takeover(path, master_generation=2)
    data = membership_signal.read_signal(path)
    # the dead master's PLAN is gone; the observed world survives
    assert data["pending_size"] is None
    assert data["trace_id"] is None
    assert data["world_size"] == 4 and data["world_version"] == 3
    assert membership_signal.master_generation(path) == 2


def test_clear_stale_on_takeover_without_file_is_noop(tmp_path):
    path = str(tmp_path / "membership_signal.json")
    assert not membership_signal.clear_stale_on_takeover(
        path, master_generation=2
    )
    assert not os.path.exists(path)


def test_lost_bind_does_not_bump_generation(tmp_path):
    """Bind-before-journal: client/local.py's _rebuild_master retries a
    lingering predecessor port by constructing a fresh Master per attempt.
    A lost bind must abandon the instance BEFORE the journal commits a
    generation bump, or every retry inflates the generation past the real
    restart count (and the e2e's generation==2 contract flakes)."""
    import socket

    import pytest

    from elasticdl_tpu.client.local import free_port
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.common.net import PortBindError
    from elasticdl_tpu.master.main import Master

    port = free_port()
    try:
        blocker = socket.socket(socket.AF_INET6, socket.SOCK_STREAM)
        blocker.bind(("::", port))
    except OSError:
        blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        blocker.bind(("0.0.0.0", port))
    blocker.listen(1)
    cfg = JobConfig(
        job_name="bind-retry",
        job_type="training_only",
        model_zoo=os.path.abspath("model_zoo"),
        model_def="mnist.mnist_cnn.custom_model",
        model_params={"learning_rate": 0.01},
        training_data="synthetic://mnist?n=100&shards=2",
        records_per_task=50,
        minibatch_size=32,
        num_epochs=1,
        num_workers=1,
        master_addr=f"localhost:{port}",
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    try:
        with pytest.raises(PortBindError):
            Master(cfg)
        # the abandoned attempt committed NOTHING to the journal
        assert not os.path.exists(
            os.path.join(str(tmp_path / "ckpt"), "control", "journal.jsonl")
        )
    finally:
        blocker.close()
    # the attempt that wins the bind is generation 1, not 1 + retries
    master = Master(cfg)
    try:
        assert master.journal.generation == 1 and not master.journal.recovered
    finally:
        master.server.stop(None)
        master.journal.close()


def test_process_manager_clears_stale_signal_at_its_own_path(tmp_path):
    """The manager writes the signal at `log_dir or checkpoint_dir`, which
    differs from Master.__init__'s checkpoint_dir-based takeover clear
    whenever log_dir is set — a recovered journal handed to a fresh manager
    must clear the dead predecessor's plan at the manager's OWN path."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.master.process_manager import ProcessManager

    log_dir = tmp_path / "logs"
    ckpt_dir = tmp_path / "ckpt"
    sig = log_dir / "membership_signal.json"
    membership_signal.write_signal(
        str(sig), world_size=2, pending_size=4, world_version=3,
        trace_id="dead-master-reform", master_generation=1,
    )
    # a journal with history replays at construction -> recovered=True
    j1 = ControlPlaneJournal(str(ckpt_dir))
    j1.append("epoch_advance", epoch=0)
    j1.close()
    j2 = ControlPlaneJournal(str(ckpt_dir))
    assert j2.recovered and j2.generation == 2

    cfg = JobConfig(num_workers=1, checkpoint_dir=str(ckpt_dir))
    ProcessManager(cfg, log_dir=str(log_dir), journal=j2)
    data = membership_signal.read_signal(str(sig))
    assert data["pending_size"] is None and data["trace_id"] is None
    assert data["world_size"] == 2 and data["world_version"] == 3
    assert membership_signal.master_generation(str(sig)) == 2
    j2.close()


# ---------------------------------------------------------------------- #
# flush-on-shutdown (ISSUE 9 satellite: the PR 7 known boundary)


def test_flush_forces_open_batch_to_disk_without_closing(tmp_path):
    """flush() must make a queued-but-unflushed record durable NOW — the
    clean-shutdown hook for records whose owner never wait()s them —
    while leaving the journal open for further commits."""
    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=8000.0)
    try:
        j.append("world_version", version=7)     # rides the 8s window
        # not yet on disk (the window has barely opened)
        lines = open(j.path, encoding="utf-8").read().splitlines()
        assert replay_lines(lines).world_version == 0
        j.flush()
        lines = open(j.path, encoding="utf-8").read().splitlines()
        assert replay_lines(lines).world_version == 7
        # the journal stays usable after a flush
        j.append("world_version", version=8).wait()
        lines = open(j.path, encoding="utf-8").read().splitlines()
        assert replay_lines(lines).world_version == 8
    finally:
        j.close()


def test_flush_is_noop_per_commit_and_empty_queue(tmp_path):
    j = ControlPlaneJournal(str(tmp_path))          # per-commit mode
    try:
        j.append("world_version", version=3)
        j.flush()                                   # no-op, no error
        lines = open(j.path, encoding="utf-8").read().splitlines()
        assert replay_lines(lines).world_version == 3
    finally:
        j.close()
    g = ControlPlaneJournal(str(tmp_path), group_commit_ms=50.0)
    try:
        g.flush()                                   # empty queue: no-op
        lines = open(g.path, encoding="utf-8").read().splitlines()
        assert not any(
            json.loads(line).get("t") == "batch" for line in lines
        )
    finally:
        g.close()


def test_process_manager_stop_flushes_newest_world_version(tmp_path):
    """A clean ProcessManager.stop() must never lose the newest
    world_version record to the group-commit window (the PR 7 boundary,
    closed): stop() flushes the journal explicitly."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.master.process_manager import ProcessManager

    j = ControlPlaneJournal(str(tmp_path), group_commit_ms=8000.0)
    cfg = JobConfig(model_def="mnist.mnist_cnn.custom_model",
                    master_addr="localhost:1")
    manager = ProcessManager(cfg, journal=j)
    try:
        # a record enqueued WITHOUT wait(), still riding the open window
        j.append("world_version", version=41)
        manager.stop(grace_s=0.5)
        lines = open(j.path, encoding="utf-8").read().splitlines()
        assert replay_lines(lines).world_version == 41
    finally:
        j.close()


# ---------------------------------------------------------------------- #
# embedding tier shard-map records (ISSUE 10): begin-without-commit
# rolls back; commit promotes; snapshot rotation carries the map


def test_emb_records_replay_committed_map():
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 1}),
        json.dumps({"t": "emb_table", "name": "users", "vocab": 1024,
                    "dim": 8, "seed": 3, "init_scale": 0.05}),
        json.dumps({"t": "emb_shard_map", "version": 1, "num_shards": 4,
                    "owners": [0, 1, 0, 1]}),
        json.dumps({"t": "emb_reshard_begin", "version": 2,
                    "owners": [0, 0, 0, 0],
                    "moves": [{"shard": 1, "src": 1, "dst": 0},
                              {"shard": 3, "src": 1, "dst": 0}]}),
        json.dumps({"t": "emb_reshard_commit", "version": 2}),
    ]
    emb = replay_lines(lines).embedding
    assert emb.version == 2
    assert emb.owners == [0, 0, 0, 0]
    assert emb.num_shards == 4
    assert not emb.reshard_interrupted
    assert emb.tables[0]["name"] == "users"


def test_emb_reshard_begin_without_commit_rolls_back():
    """Master killed mid-resharding: the replayed map is the last
    COMMITTED one, flagged interrupted so clients conservatively requeue
    in-flight pushes (store seq fencing dedupes the re-sends)."""
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 1}),
        json.dumps({"t": "emb_shard_map", "version": 1, "num_shards": 4,
                    "owners": [0, 1, 0, 1]}),
        json.dumps({"t": "emb_reshard_begin", "version": 2,
                    "owners": [0, 0, 0, 0],
                    "moves": [{"shard": 1, "src": 1, "dst": 0}]}),
    ]
    emb = replay_lines(lines).embedding
    assert emb.version == 1
    assert emb.owners == [0, 1, 0, 1]
    assert emb.reshard_interrupted is True


def test_emb_commit_without_begin_is_ignored():
    lines = [
        json.dumps({"t": "header", "v": 1, "generation": 1}),
        json.dumps({"t": "emb_shard_map", "version": 1, "num_shards": 2,
                    "owners": [0, 0]}),
        json.dumps({"t": "emb_reshard_commit", "version": 9}),
    ]
    emb = replay_lines(lines).embedding
    assert emb.version == 1 and emb.owners == [0, 0]


def test_emb_snapshot_rotation_round_trip(tmp_path):
    """A second takeover restores the map from the FIRST takeover's
    compacted snapshot (no raw records left), interrupted flag included."""
    j1 = ControlPlaneJournal(str(tmp_path))
    j1.append("emb_table", name="users", vocab=1024, dim=8, seed=0,
              init_scale=0.05)
    j1.append("emb_shard_map", version=1, num_shards=4,
              owners=[0, 1, 0, 1])
    j1.append("emb_reshard_begin", version=2, owners=[0, 0, 0, 0],
              moves=[{"shard": 1, "src": 1, "dst": 0}])
    j1.abort()                                  # crash mid-resharding
    j2 = ControlPlaneJournal(str(tmp_path))     # takeover 1: replays
    emb = j2.embedding_snapshot()
    assert emb.reshard_interrupted and emb.version == 1
    j2.close()
    j3 = ControlPlaneJournal(str(tmp_path))     # takeover 2: snapshot only
    emb2 = j3.embedding_snapshot()
    assert emb2.version == 1
    assert emb2.owners == [0, 1, 0, 1]
    assert emb2.reshard_interrupted is True
    assert emb2.tables[0]["name"] == "users"
    j3.close()


def test_emb_torn_begin_line_drops_whole(tmp_path):
    """A torn emb_reshard_begin tail is dropped whole — the replay sees
    only the committed map, with no interruption to flag."""
    j = ControlPlaneJournal(str(tmp_path))
    j.append("emb_shard_map", version=1, num_shards=2, owners=[0, 0])
    j.close()
    with open(j.path, "a", encoding="utf-8") as f:
        f.write('{"t": "emb_reshard_begin", "version": 2, "own')
    with open(j.path, encoding="utf-8") as f:
        res = replay_lines(f.readlines())
    assert res.dropped_lines == 1
    assert res.embedding.version == 1
    assert res.embedding.reshard_interrupted is False


# ---------------------------------------------------------------------- #
# ProcessManager world_version crash consistency (ISSUE 10 satellite:
# the PR 7 known boundary closed for real — commit awaited outside the
# lock, BEFORE the version becomes observable)


class _FakeProc:
    pid = 4242

    def poll(self):
        return None

    def kill(self):
        pass

    def wait(self, timeout=None):
        return 0


def _reform_manager(tmp_path, journal, monkeypatch):
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.master import process_manager as pm

    monkeypatch.setattr(
        pm.ProcessManager, "_spawn",
        lambda self, worker_id, relaunches=0, process_id=0: pm._WorkerProc(
            worker_id=worker_id, proc=_FakeProc(), relaunches=relaunches,
        ),
    )
    cfg = JobConfig(model_def="mnist.mnist_cnn.custom_model",
                    master_addr="localhost:1", num_processes=2)
    sig = str(tmp_path / "membership_signal.json")
    return pm.ProcessManager(
        cfg, journal=journal, membership_signal_path=sig), sig


def test_reform_world_version_durable_before_announce(
    tmp_path, monkeypatch
):
    """Group-commit mode: _reform_cohort must fsync the world_version
    record BEFORE the announcement (or any spawned env) can carry it —
    after the reform returns, a successor's replay of the journal file
    as-is must already hold the announced version."""
    j = ControlPlaneJournal(
        str(tmp_path / "ckpt"), group_commit_ms=5.0)
    manager, sig = _reform_manager(tmp_path, j, monkeypatch)
    try:
        manager._reform_cohort(2, 2, "test")
        announced = membership_signal.read_signal(sig)["world_version"]
        assert announced == 1
        # the journal FILE (not a flushed/closed copy) already carries it
        with open(j.path, encoding="utf-8") as f:
            assert replay_lines(f.readlines()).world_version == announced
    finally:
        j.close()


def test_reform_never_announces_undurable_world_version(
    tmp_path, monkeypatch
):
    """The crash-consistency pin: when the commit CANNOT be made durable
    (committer finds the journal stuck/closed), the reform aborts
    un-announced — an announced world version can never be one a
    successor's replay lacks."""
    import pytest as _pytest

    from elasticdl_tpu.master.journal import JournalCommitError

    j = ControlPlaneJournal(
        str(tmp_path / "ckpt"), group_commit_ms=5.0)
    manager, sig = _reform_manager(tmp_path, j, monkeypatch)
    before = membership_signal.read_signal(sig)
    # wedge the journal under the committer: flush fails -> poisoned ->
    # the parked commit's wait() raises
    with j._lock:
        j._fh.close()
        j._fh = None
    with _pytest.raises(JournalCommitError):
        manager._reform_cohort(2, 2, "test")
    after = membership_signal.read_signal(sig)
    # nothing announced, nothing spawned
    assert (after or {}).get("world_version") == (
        (before or {}).get("world_version")
    )
    with manager._lock:
        assert not manager._procs
