"""k8s instance manager: pod lifecycle state machine against a scripted watch
stream, plus manifest render tests for every TPU type.

Mirrors the reference's test stance (SURVEY §4): the k8s API is faked
in-process, the manager/membership/dispatcher wiring is real — so the test
proves pod death drives task recovery through the actual callback chain, with
no heartbeat timeout involved.
"""

import os
import queue
import threading
import time

import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.constants import PodStatus
from elasticdl_tpu.master.k8s_instance_manager import (
    K8sApi,
    K8sInstanceManager,
    PodEvent,
)
from elasticdl_tpu.master.membership import Membership
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher


class FakeApi(K8sApi):
    """Scripted k8s: records create/delete calls, serves queued events."""

    def __init__(self):
        self.created = []          # manifests, in call order
        self.deleted = []          # pod names
        self.events: "queue.Queue[PodEvent]" = queue.Queue()

    def create_pod(self, manifest):
        self.created.append(manifest)

    def delete_pod(self, name):
        self.deleted.append(name)

    def watch_pods(self, label_selector, stop):
        while not stop.is_set():
            try:
                yield self.events.get(timeout=0.05)
            except queue.Empty:
                continue

    # -- helpers -------------------------------------------------------- #

    def push(self, name, phase, type_="MODIFIED"):
        self.events.put(PodEvent(type=type_, name=name, phase=phase))

    def created_names(self):
        return [m["metadata"]["name"] for m in self.created]


def make_cfg(**overrides):
    base = dict(
        job_name="kj",
        model_def="mnist.mnist_cnn.custom_model",
        num_workers=2,
        relaunch_max=2,
        image_name="img:latest",
        job_type="evaluation_only",   # plain multi-worker stays valid
    )
    base.update(overrides)
    return JobConfig(**base)


def wait_for(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return False


@pytest.fixture
def manager_setup():
    cfg = make_cfg()
    api = FakeApi()
    membership = Membership(heartbeat_timeout_s=3600)  # reaper never fires
    dispatcher = TaskDispatcher(
        training_shards=[("s", 0, 100)],
        evaluation_shards=[],
        prediction_shards=[],
        records_per_task=25,
        num_epochs=1,
    )
    membership.add_death_callback(dispatcher.recover_tasks)
    mgr = K8sInstanceManager(cfg, membership=membership, api=api)
    yield cfg, api, membership, dispatcher, mgr
    mgr._stop.set()


def _count_worker(api, wid):
    return sum(
        1 for n in api.created_names() if n.startswith(f"kj-worker-{wid}-g")
    )


def test_start_creates_worker_pods(manager_setup):
    cfg, api, _m, _d, mgr = manager_setup
    mgr.start_workers()
    # generation-suffixed names: relaunches must be NEW pod objects, not
    # kubectl-apply no-ops onto the dead pod
    assert api.created_names() == ["kj-worker-0-g0", "kj-worker-1-g0"]
    # specs are master-managed pods: relaunch accounting is the manager's
    assert all(m["spec"]["restartPolicy"] == "Never" for m in api.created)
    assert all(m["metadata"]["labels"]["role"] == "worker" for m in api.created)


def test_pod_failure_drives_task_recovery_without_heartbeat(manager_setup):
    """The round-3 'done' criterion (VERDICT #4): a FAILED pod event recovers
    the dead worker's leased tasks immediately — membership's heartbeat
    timeout is 1h here, so only the watch path can be responsible."""
    cfg, api, membership, dispatcher, mgr = manager_setup
    mgr.start_workers()
    membership.register("pod-1", preferred_id=1)
    task = dispatcher.get(worker_id=1)
    assert task is not None
    assert dispatcher.counts()["doing"] == 1

    api.push("kj-worker-1-g0", "Failed")
    assert wait_for(lambda: dispatcher.counts()["doing"] == 0)
    assert dispatcher.counts()["todo"] == 4  # the lease went back to todo
    # the pod was relaunched within budget, as the NEXT generation, and the
    # dead pod object was cleaned up
    assert wait_for(lambda: "kj-worker-1-g1" in api.created_names())
    assert "kj-worker-1-g0" in api.deleted


def test_relaunch_budget_exhaustion_marks_failed(manager_setup):
    cfg, api, _m, _d, mgr = manager_setup
    mgr.start_workers()
    for gen in range(cfg.relaunch_max + 1):
        api.push(f"kj-worker-0-g{gen}", "Failed")
        wait_for(lambda: "kj-worker-0-g%d" % (gen + 1) in api.created_names()
                 or mgr.statuses().get(0) == PodStatus.FAILED)
    assert wait_for(lambda: mgr.statuses().get(0) == PodStatus.FAILED)
    # budget = relaunch_max creations beyond the original
    assert _count_worker(api, 0) == 1 + cfg.relaunch_max

    # watch-reconnect replay (code-review round 3): the budget-exhausted
    # worker's Failed pod lingers and re-lists as ADDED/Failed on every
    # reconnect — FAILED must stay terminal (no extra relaunch, no status
    # flip), exactly like the DELETED branch
    last = f"kj-worker-0-g{cfg.relaunch_max}"
    # drain the job so _job_finished_fn() is true — the un-guarded path
    # would now flip FAILED -> SUCCEEDED on the replayed event
    while True:
        t = _d.get(worker_id=1)
        if t is None:
            break
        _d.report(t.task_id, 1, True)
    assert _d.finished()
    mgr._job_finished_fn = _d.finished  # the fixture wires api only
    api.push(last, "Failed", type_="ADDED")
    api.push(last, "Failed", type_="ADDED")
    time.sleep(0.3)
    assert mgr.statuses().get(0) == PodStatus.FAILED
    assert _count_worker(api, 0) == 1 + cfg.relaunch_max


def test_deleted_event_and_succeeded_are_terminal(manager_setup):
    cfg, api, _m, _d, mgr = manager_setup
    mgr.start_workers()
    api.push("kj-worker-0-g0", "Running")
    assert wait_for(lambda: mgr.statuses().get(0) == PodStatus.RUNNING)
    # DELETED while running = eviction: relaunch
    api.push("kj-worker-0-g0", "Running", type_="DELETED")
    assert wait_for(lambda: "kj-worker-0-g1" in api.created_names())
    # Succeeded then DELETED (GC) must NOT relaunch
    api.push("kj-worker-1-g0", "Succeeded")
    assert wait_for(lambda: mgr.statuses().get(1) == PodStatus.SUCCEEDED)
    api.push("kj-worker-1-g0", "Succeeded", type_="DELETED")
    time.sleep(0.2)
    assert _count_worker(api, 1) == 1
    assert mgr.statuses()[1] == PodStatus.SUCCEEDED


def test_stale_generation_events_ignored(manager_setup):
    """A late DELETED for a replaced pod must not kill the healthy
    replacement (review finding: events were keyed on name+status only)."""
    cfg, api, membership, dispatcher, mgr = manager_setup
    mgr.start_workers()
    api.push("kj-worker-0-g0", "Failed")           # relaunch -> g1
    assert wait_for(lambda: "kj-worker-0-g1" in api.created_names())
    api.push("kj-worker-0-g1", "Running")
    assert wait_for(lambda: mgr.statuses().get(0) == PodStatus.RUNNING)
    # GC finally deletes the old Failed pod: must be a no-op
    api.push("kj-worker-0-g0", "Failed", type_="DELETED")
    time.sleep(0.3)
    assert mgr.statuses()[0] == PodStatus.RUNNING
    assert "kj-worker-0-g2" not in api.created_names()


def test_add_and_remove_worker(manager_setup):
    cfg, api, _m, _d, mgr = manager_setup
    mgr.start_workers()
    wid = mgr.add_worker()
    assert wid == 2 and "kj-worker-2-g0" in api.created_names()
    mgr.remove_worker(2)
    assert "kj-worker-2-g0" in api.deleted
    # the DELETED event arrives; a deliberate scale-in terminates as DELETED
    # (NOT a failure — all_failed() must stay false) and never relaunches
    api.push("kj-worker-2-g0", "Running", type_="DELETED")
    assert wait_for(lambda: mgr.statuses().get(2) == PodStatus.DELETED)
    assert _count_worker(api, 2) == 1
    assert not mgr.all_failed()


def test_stop_deletes_pods(manager_setup):
    cfg, api, _m, _d, mgr = manager_setup
    mgr.start_workers()
    mgr.stop(grace_s=1)
    assert set(api.deleted) >= {"kj-worker-0-g0", "kj-worker-1-g0"}


# ---------------------------------------------------------------------- #
# manifest rendering


def test_render_worker_pod_every_tpu_type():
    from elasticdl_tpu.client.k8s import TPU_TYPES, render_worker_pod

    for tpu_type, (accel, topology, hosts, chips) in TPU_TYPES.items():
        cfg = make_cfg(tpu_type=tpu_type)
        if hosts > 1:
            # managed pods can't address a multi-host cohort; only the
            # StatefulSet flavor may host those slices
            with pytest.raises(ValueError, match="StatefulSet"):
                render_worker_pod(cfg, 3)
            continue
        pod = render_worker_pod(cfg, 3)
        spec = pod["spec"]
        assert spec["nodeSelector"]["cloud.google.com/gke-tpu-accelerator"] == accel
        assert spec["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == topology
        c = spec["containers"][0]
        assert c["resources"]["requests"]["google.com/tpu"] == str(chips)
        assert c["resources"]["limits"]["google.com/tpu"] == str(chips)
        env = {e["name"]: e["value"] for e in c["env"]}
        assert env["EDL_WORKER_ID"] == "3"
        # argv carries the in-cluster master address
        args = c["args"]
        assert "--master_addr" in args
        assert args[args.index("--master_addr") + 1].startswith("kj-master:")


def test_render_statefulset_every_tpu_type_and_override_warning():
    from elasticdl_tpu.client.k8s import TPU_TYPES, render_worker_statefulset

    for tpu_type, (accel, topology, hosts, chips) in TPU_TYPES.items():
        cfg = make_cfg(tpu_type=tpu_type, num_workers=1)
        headless, sts = render_worker_statefulset(cfg)
        assert headless["spec"]["clusterIP"] == "None"
        assert sts["spec"]["replicas"] == hosts
        tmpl = sts["spec"]["template"]["spec"]
        assert tmpl["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == topology
        c = tmpl["containers"][0]
        assert c["resources"]["requests"]["google.com/tpu"] == str(chips)

    # tpu_type overriding a non-default num_workers warns (VERDICT weak #9);
    # the package root logger is propagate=False, so listen on the module's
    # logger directly instead of caplog
    import logging

    records = []
    handler = logging.Handler()
    handler.emit = records.append
    klog = logging.getLogger("elasticdl_tpu.client.k8s")
    klog.addHandler(handler)
    try:
        render_worker_statefulset(make_cfg(tpu_type="v5e-32", num_workers=3))
    finally:
        klog.removeHandler(handler)
    assert any("ignoring num_workers" in r.getMessage() for r in records)


def test_unknown_tpu_type_raises():
    from elasticdl_tpu.client.k8s import render_worker_pod, render_worker_statefulset

    with pytest.raises(ValueError, match="unknown tpu_type"):
        render_worker_statefulset(make_cfg(tpu_type="v9-999"))
    with pytest.raises(ValueError, match="unknown tpu_type"):
        render_worker_pod(make_cfg(tpu_type="v9-999"), 0)


def test_statefulset_multihost_slice_is_one_cohort():
    """Review fix: a multi-host TPU slice renders as ONE SPMD cohort (the
    renderer decides replicas, so it must also enforce the no-divergent-
    replicas rule that JobConfig.validate enforces for num_workers)."""
    from elasticdl_tpu.client.k8s import render_worker_statefulset

    cfg = make_cfg(tpu_type="v5e-32", num_workers=1,
                   job_type="training_with_evaluation")
    headless, sts = render_worker_statefulset(cfg)
    assert sts["spec"]["replicas"] == 8
    c = sts["spec"]["template"]["spec"]["containers"][0]
    args = c["args"]
    assert args[args.index("--num_processes") + 1] == "8"
    env = {e["name"]: e["value"] for e in c["env"]}
    assert env["EDL_PROCESS_ID_FROM_HOSTNAME"] == "1"
    # inconsistent explicit num_processes is an error, not a silent override
    with pytest.raises(ValueError, match="host slice"):
        render_worker_statefulset(make_cfg(tpu_type="v5e-32", num_processes=3))
    # single-host slice stays a plain worker (no cohort env)
    _h, sts1 = render_worker_statefulset(make_cfg(tpu_type="v5e-4"))
    env1 = {e["name"]: e["value"]
            for e in sts1["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert "EDL_PROCESS_ID_FROM_HOSTNAME" not in env1


def test_cohort_process_id_from_hostname(monkeypatch):
    import socket

    from elasticdl_tpu.parallel.elastic import context_from_env

    cfg = make_cfg(num_processes=4)
    monkeypatch.setenv("EDL_PROCESS_ID_FROM_HOSTNAME", "1")
    monkeypatch.delenv("EDL_PROCESS_ID", raising=False)
    monkeypatch.setattr(socket, "gethostname", lambda: "kj-worker-2")
    ctx = context_from_env(cfg)
    assert ctx is not None and ctx.process_id == 2 and ctx.num_processes == 4
    assert "EDL_PROCESS_ID" not in os.environ
    monkeypatch.setattr(socket, "gethostname", lambda: "nodigit")
    with pytest.raises(RuntimeError, match="no trailing ordinal"):
        context_from_env(cfg)
    assert "EDL_PROCESS_ID" not in os.environ


@pytest.mark.parametrize(
    "env,num_processes,expected",
    [
        # StatefulSet pod: the ordinal comes from the hostname
        ({"EDL_PROCESS_ID_FROM_HOSTNAME": "1"}, 4, (3, 4)),
        # a cohort resized to one process is still a cohort
        ({"EDL_PROCESS_ID": "1", "EDL_NUM_PROCESSES": "1"}, 4, (1, 1)),
        # a plain one-process worker
        ({}, 1, None),
    ],
    ids=["from_hostname", "resized_to_one", "plain_worker"],
)
def test_context_from_env_reads_and_never_writes(
    monkeypatch, env, num_processes, expected
):
    import socket

    from elasticdl_tpu.parallel.elastic import context_from_env

    for key in ("EDL_PROCESS_ID", "EDL_PROCESS_ID_FROM_HOSTNAME",
                "EDL_NUM_PROCESSES", "EDL_COORDINATOR_ADDR",
                "EDL_WORLD_VERSION"):
        monkeypatch.delenv(key, raising=False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    monkeypatch.setattr(socket, "gethostname", lambda: "kj-worker-3")
    before = dict(os.environ)
    ctx = context_from_env(make_cfg(num_processes=num_processes))
    assert dict(os.environ) == before
    got = None if ctx is None else (ctx.process_id, ctx.num_processes)
    assert got == expected


def test_statefulset_cohort_without_tpu_type_and_single_host_guard():
    """Review fix: num_processes>1 must shape the StatefulSet even without a
    multi-host TPU slice, and a single-host slice rejects num_processes>1."""
    from elasticdl_tpu.client.k8s import render_worker_statefulset

    _h, sts = render_worker_statefulset(make_cfg(num_processes=4, num_workers=1))
    assert sts["spec"]["replicas"] == 4
    env = {e["name"]: e["value"]
           for e in sts["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert env["EDL_PROCESS_ID_FROM_HOSTNAME"] == "1"
    with pytest.raises(ValueError, match="single-host"):
        render_worker_statefulset(make_cfg(tpu_type="v5e-4", num_processes=4))


def test_k8s_add_worker_rejected_for_plain_training():
    api = FakeApi()
    cfg = make_cfg(job_type="training_with_evaluation", num_workers=1)
    mgr = K8sInstanceManager(cfg, api=api)
    with pytest.raises(RuntimeError, match="cohort"):
        mgr.add_worker()


def test_master_owns_k8s_instance_manager(tmp_path):
    """Review fix: --instance_manager=k8s makes the MASTER create and watch
    worker pods (previously the module had no production caller), and the
    manifest renderer then omits the StatefulSet."""
    from elasticdl_tpu.client.k8s import render_job_manifests
    from elasticdl_tpu.client.local import free_port
    from elasticdl_tpu.master.main import Master

    # evaluation_only keeps plain num_workers=2 valid; start() injects the
    # eval tasks the leased worker then holds
    cfg = make_cfg(
        instance_manager="k8s",
        job_name="kmj",
        validation_data="synthetic://mnist?n=100&shards=1",
        records_per_task=25,
        master_addr=f"localhost:{free_port()}",
        num_workers=2,
    )
    # manifests: master only — workers are master-managed pods
    kinds = [(m["kind"], m["metadata"]["name"]) for m in render_job_manifests(cfg)]
    assert ("StatefulSet", "kmj-worker") not in kinds
    assert ("Pod", "kmj-master") in kinds
    # the flag rides the argv chain to the master process
    args = render_job_manifests(cfg)[0]["spec"]["containers"][0]["args"]
    assert args[args.index("--instance_manager") + 1] == "k8s"

    api = FakeApi()
    master = Master(cfg, k8s_api=api)
    master.start()
    try:
        assert master.instance_manager is not None
        assert api.created_names() == ["kmj-worker-0-g0", "kmj-worker-1-g0"]
        # pod death drives task recovery through the master's own manager
        master.membership.register("pod-1", preferred_id=1)
        task = master.dispatcher.get(worker_id=1)
        assert task is not None
        api.push("kmj-worker-1-g0", "Failed")
        assert wait_for(lambda: master.dispatcher.counts()["doing"] == 0)
        assert wait_for(lambda: "kmj-worker-1-g1" in api.created_names())
    finally:
        master.shutdown(grace_s=1)
        master.server.stop(0)
    # shutdown tore the pods down
    assert any(n.startswith("kmj-worker-0") for n in api.deleted)


# --------------------------------------------------------------------- #
# VERDICT r4 weak #6: grow scripted-stream coverage — kubectl wire parsing
# against a REAL subprocess pipe, watch-failure reconnects, re-list
# idempotence.


FAKE_KUBECTL = r'''#!/usr/bin/env python3
"""Fake kubectl: emits a watch stream with adversarial segmentation —
a document split mid-way, a multi-byte UTF-8 character split across
writes, and two documents concatenated in one write."""
import json, sys, time

w = sys.stdout.buffer


def doc(tp, name, phase, note=None):
    meta = {"name": name}
    if note is not None:
        meta["annotations"] = {"note": note}
    return json.dumps(
        {"type": tp, "object": {"metadata": meta, "status": {"phase": phase}}},
        ensure_ascii=False,
    ).encode("utf-8")


d1 = doc("ADDED", "kj-worker-0-g0", "Pending")
w.write(d1[:10]); w.flush(); time.sleep(0.15)
w.write(d1[10:]); w.flush()

d2 = doc("MODIFIED", "kj-worker-0-g0", "Running", note="héllo")
cut = d2.index("é".encode("utf-8")) + 1   # mid 2-byte sequence
w.write(d2[:cut]); w.flush(); time.sleep(0.15)
w.write(d2[cut:]); w.flush()

w.write(doc("MODIFIED", "kj-worker-1-g0", "Failed")
        + doc("DELETED", "kj-worker-1-g0", "Failed"))
w.flush()
time.sleep(5)   # stay alive until the watcher's stop kills us
'''


def test_kubectl_watch_stream_parses_real_subprocess(tmp_path):
    """The incremental UTF-8 + JSON decode behind `kubectl --watch
    --output-watch-events -o json`, driven through a real pipe with
    adversarial write boundaries."""
    import stat

    from elasticdl_tpu.master.k8s_instance_manager import KubectlApi

    script = tmp_path / "kubectl"
    script.write_text(FAKE_KUBECTL)
    script.chmod(script.stat().st_mode | stat.S_IEXEC)

    api = KubectlApi.__new__(KubectlApi)
    api._ns = "default"
    api._kubectl = str(script)
    api._watch_procs = []

    stop = threading.Event()
    events = []
    for ev in api.watch_pods("app=kj", stop):
        events.append(ev)
        if len(events) == 4:
            stop.set()
    api.close()

    assert [(e.type, e.name, e.phase) for e in events] == [
        ("ADDED", "kj-worker-0-g0", "Pending"),
        ("MODIFIED", "kj-worker-0-g0", "Running"),
        ("MODIFIED", "kj-worker-1-g0", "Failed"),
        ("DELETED", "kj-worker-1-g0", "Failed"),
    ]
    assert not api._watch_procs   # child reaped on generator exit


class FlakyApi(FakeApi):
    """Watch stream that dies after each event until `fail_times` runs
    out — the apiserver-hiccup / kubectl-restart case."""

    def __init__(self, fail_times=1):
        super().__init__()
        self.fail_times = fail_times
        self.connects = 0

    def watch_pods(self, label_selector, stop):
        self.connects += 1
        served = 0
        while not stop.is_set():
            try:
                ev = self.events.get(timeout=0.05)
            except queue.Empty:
                continue
            yield ev
            served += 1
            if self.fail_times > 0:
                self.fail_times -= 1
                raise RuntimeError("watch stream torn down")


def test_watch_stream_failure_reconnects_and_recovers(manager_setup):
    """A watch stream that raises mid-event-loop must reconnect (loop, not
    crash) and later events must still drive pod-death recovery."""
    cfg, _api, membership, dispatcher, _mgr = manager_setup
    api = FlakyApi(fail_times=1)
    mgr = K8sInstanceManager(cfg, membership=membership, api=api)
    mgr.start_workers()
    try:
        # worker 1 registers, then its pod fails AFTER the first stream
        # death (the event arrives on the reconnected stream)
        membership.register("pod-w1", preferred_id=1)
        task = dispatcher.get(worker_id=1)
        api.push("kj-worker-0-g0", "Running")      # served, then stream dies
        assert wait_for(lambda: api.connects >= 2), "no reconnect"
        api.push("kj-worker-1-g0", "Failed")       # post-reconnect event
        assert wait_for(lambda: _count_worker(api, 1) == 2), "no relaunch"
        assert wait_for(
            lambda: dispatcher.counts()["doing"] == 0
        ), "task not recovered after post-reconnect pod death"
    finally:
        mgr._stop.set()


def test_reconnect_relist_of_running_pods_is_idempotent(manager_setup):
    """Every reconnect re-lists live pods as ADDED; re-listed Running pods
    of the CURRENT generation must not trigger relaunches or deaths."""
    cfg, api, _membership, _dispatcher, mgr = manager_setup
    mgr.start_workers()
    try:
        for _ in range(3):   # three reconnect-style re-lists
            api.push("kj-worker-0-g0", "Running", type_="ADDED")
            api.push("kj-worker-1-g0", "Running", type_="ADDED")
        assert wait_for(
            lambda: mgr.statuses().get(0) == PodStatus.RUNNING
            and mgr.statuses().get(1) == PodStatus.RUNNING
        )
        time.sleep(0.3)   # let any spurious relaunch surface
        assert _count_worker(api, 0) == 1 and _count_worker(api, 1) == 1
        assert not api.deleted
    finally:
        mgr._stop.set()
