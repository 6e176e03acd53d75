"""Elastic job supervisor end-to-end (the missing elasticity loop of
ISSUE 1): heartbeat liveness, backoff, restart-from-checkpoint, and
crash-loop abandonment, all in one CI process tree (SURVEY §4.4).

The job under supervision is defined in supervisor_worker.py: N workers
drain one coordinator queue of gradient shards into per-worker float64
accumulators. Its invariant — `sum over workers of acc` equals an
uninterrupted baseline run bit-for-bit up to summation order — is what
lets these tests demand EXACT recovery, not just "it finished"."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed import (
    Coordinator,
    CoordinatorServer,
    RemoteCoordinator,
    Supervisor,
    checkpoint as ckpt,
)

WORKER_PY = os.path.join(os.path.dirname(__file__), "supervisor_worker.py")


# ---------------------------------------------------------------------------
# RemoteCoordinator retry/backoff (satellite: flaky-server fixture)
# ---------------------------------------------------------------------------


class _FlakyServer(object):
    """Accepts TCP connections and drops the first `drop_first` of them
    cold (accept-then-close, the signature of a service that is up but
    not ready); later connections speak the coordinator's newline-JSON
    protocol (ping only)."""

    def __init__(self, drop_first):
        self.drop_first = drop_first
        self.connections = 0
        self._stop = False
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self.connections += 1
            if self.connections <= self.drop_first:
                conn.close()
                continue
            f = conn.makefile("rwb")
            while True:
                line = f.readline()
                if not line:
                    break
                json.loads(line)
                f.write(b'{"ok": true, "result": "pong"}\n')
                f.flush()
            conn.close()

    def close(self):
        self._stop = True
        try:
            self.sock.close()
        except OSError:
            pass


def test_remote_coordinator_recovers_from_dropped_connections():
    srv = _FlakyServer(drop_first=3)
    try:
        cli = RemoteCoordinator(
            "127.0.0.1:%d" % srv.port,
            retry_deadline_s=10.0, backoff_base_s=0.02,
        )
        t0 = time.monotonic()
        assert cli.ping() == "pong"
        elapsed = time.monotonic() - t0
        assert elapsed < 8.0, "recovered, but not within its deadline"
        # exactly drop_first failures + 1 success: backoff retried, the
        # old reconnect-exactly-once client would have raised
        assert srv.connections == 4
        cli.close()
    finally:
        srv.close()


def test_remote_coordinator_deadline_bounds_silent_server():
    """A server that ACCEPTS but never replies must not hold a call for
    the full transport timeout_s: the per-call retry deadline bounds the
    blocking read too, not just connects and backoff sleeps."""
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)  # connections accepted by the kernel, never serviced
    try:
        cli = RemoteCoordinator(
            "127.0.0.1:%d" % srv.getsockname()[1],
            timeout_s=30.0, retry_deadline_s=0.5, backoff_base_s=0.02,
        )
        t0 = time.monotonic()
        with pytest.raises((OSError, ConnectionError)):
            cli.ping()
        assert time.monotonic() - t0 < 5.0, \
            "silent server held the call past its retry deadline"
    finally:
        srv.close()


def test_remote_coordinator_call_deadline_bounds_retries():
    srv = _FlakyServer(drop_first=10 ** 9)  # never becomes ready
    try:
        cli = RemoteCoordinator(
            "127.0.0.1:%d" % srv.port,
            retry_deadline_s=0.6, backoff_base_s=0.02, backoff_max_s=0.1,
        )
        t0 = time.monotonic()
        with pytest.raises((OSError, ConnectionError)):
            cli.ping()
        elapsed = time.monotonic() - t0
        assert elapsed < 5.0, "deadline did not bound the retry loop"
        assert srv.connections >= 2, "no retry happened at all"
    finally:
        srv.close()


def _poll_until(sup, pred, timeout_s):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        sup.poll()
        if pred():
            return True
        time.sleep(0.02)
    return pred()


def test_supervisor_blind_view_never_hang_kills():
    """With NO membership view at all — coordinator=None, or one that
    raises on every membership() call — hang detection is disabled: a
    healthy worker past spawn_grace_s must NOT be SIGKILLed."""

    class _Bouncing(object):
        def membership(self):
            raise ConnectionError("partitioned")

    argv = [sys.executable, "-c", "import time; time.sleep(30)"]
    for coord in (None, _Bouncing()):
        sup = Supervisor(lambda wid: argv, ["w0"], coordinator=coord,
                         spawn_grace_s=0.05)
        sup.start()
        try:
            time.sleep(0.2)  # well past the (tiny) spawn grace
            sup.poll()
            h = sup.handles["w0"]
            assert h.running and h.hang_kills == 0, (coord, h.summary())
        finally:
            sup.stop()


def test_supervisor_coordinator_bounce_spares_registered_workers():
    """A coordinator restart loses its (ephemeral) membership registry:
    a worker that HAD registered then vanished from the view must not be
    hang-killed — it re-registers on its next heartbeat. Only a worker
    never seen at all falls under the spawn grace."""
    view = {}

    class _Bouncy(object):
        def membership(self):
            return dict(view)

    argv = [sys.executable, "-c", "import time; time.sleep(30)"]
    sup = Supervisor(lambda wid: argv, ["w0"], coordinator=_Bouncy(),
                     spawn_grace_s=0.05)
    sup.start()
    try:
        h = sup.handles["w0"]
        # the worker registers and heartbeats...
        view["w0"] = {"incarnation": 1, "last_seen": time.time() + 1,
                      "alive": True}
        sup.poll()
        # ...then the coordinator bounces: registry gone, worker old
        view.clear()
        time.sleep(0.2)  # well past the (tiny) spawn grace
        sup.poll()
        assert h.running and h.hang_kills == 0, h.summary()
    finally:
        sup.stop()


def test_supervisor_incarnation_collision_spares_alive_worker():
    """Coordinator bounce + incarnation collision: the replacement
    re-registers at the SAME incarnation number the supervisor
    snapshotted from the predecessor's stale record. An actively-alive
    record under our worker id can only be our process — it must not be
    grace-killed as 'never registered'; once its refreshes stop, the
    expiry is still detected."""
    view = {"w0": {"incarnation": 1, "alive": True}}

    class _Stub(object):
        def membership(self):
            return {k: dict(v) for k, v in view.items()}

    argv = [sys.executable, "-c", "import time; time.sleep(30)"]
    sup = Supervisor(lambda wid: argv, ["w0"], coordinator=_Stub(),
                     spawn_grace_s=0.05, restart_max=1)
    sup.start()  # snapshots spawn_incarnation=1 from the 'stale' record
    try:
        h = sup.handles["w0"]
        assert h.spawn_incarnation == 1
        time.sleep(0.2)  # past the grace, record still alive
        sup.poll()
        assert h.running and h.hang_kills == 0, h.summary()
        view["w0"]["alive"] = False  # heartbeats stop: expiry fires
        assert _poll_until(sup, lambda: h.hang_kills >= 1, timeout_s=10.0)
    finally:
        sup.stop()


def test_supervisor_blind_spawn_resnapshot_spares_healed_partition():
    """DEFERRED PR-1 bug (CHANGES.md): a worker respawned while the
    membership view is blind used to snapshot spawn_incarnation=None, so
    when the partition healed, the dead predecessor's EXPIRED record
    (incarnation != None) condemned the healthy replacement — repeated
    partitions at respawn time walked rapid_failures to abandonment.
    The blind-spawn sentinel defers the snapshot to the first visible
    sweep; the stale record becomes the baseline instead of a verdict.
    A real later registration still vouches for — and condemns — the
    process exactly as before."""
    from paddle_tpu.distributed import supervisor as sup_mod

    state = {"blind": True}
    # the dead predecessor's record: expired, from before the partition
    view = {"w0": {"incarnation": 3, "alive": False}}

    class _Healing(object):
        def membership(self):
            if state["blind"]:
                raise ConnectionError("partitioned")
            return {k: dict(v) for k, v in view.items()}

    argv = [sys.executable, "-c", "import time; time.sleep(30)"]
    sup = Supervisor(lambda wid: argv, ["w0"], coordinator=_Healing(),
                     spawn_grace_s=60.0, restart_max=2)
    sup.start()  # view is blind: the spawn CANNOT snapshot a baseline
    try:
        h = sup.handles["w0"]
        assert h.spawn_incarnation is sup_mod._BLIND_SPAWN
        state["blind"] = False  # partition heals; stale record visible
        sup.poll()
        # the healed sweep re-snapshots instead of killing
        assert h.running and h.hang_kills == 0, h.summary()
        assert h.spawn_incarnation == 3
        sup.poll()  # and stays calm on later sweeps
        assert h.running and h.hang_kills == 0, h.summary()
        # the process now actually registers (incarnation bumps)...
        view["w0"] = {"incarnation": 4, "alive": True}
        sup.poll()
        assert h.running and h.hang_kills == 0
        # ...and when ITS heartbeats stop, detection still fires
        view["w0"]["alive"] = False
        assert _poll_until(sup, lambda: h.hang_kills >= 1, timeout_s=10.0)
    finally:
        sup.stop()


def test_supervisor_membership_poll_bounded_during_partition():
    """Supervision must keep sweeping during a partition: _membership
    clamps a RemoteCoordinator's per-call retry deadline (default 30 s)
    to membership_deadline_s, and restores it afterwards."""
    cli = RemoteCoordinator("127.0.0.1:9", retry_deadline_s=30.0,
                            backoff_base_s=0.02)  # port 9: discard/refused
    sup = Supervisor(lambda wid: ["true"], ["w0"], coordinator=cli,
                     membership_deadline_s=0.5)
    t0 = time.monotonic()
    assert sup._membership() is None
    assert time.monotonic() - t0 < 5.0, \
        "membership poll sat in the client's full retry loop"
    assert cli.retry_deadline_s == 30.0  # restored


def test_supervisor_start_is_idempotent():
    """start()+run() (run() calls start() itself) must not double-spawn
    a worker and orphan the first process."""
    argv = [sys.executable, "-c", "import time; time.sleep(30)"]
    sup = Supervisor(lambda wid: argv, ["w0"])
    sup.start()
    try:
        pid = sup.handles["w0"].proc.pid
        sup.start()
        assert sup.handles["w0"].proc.pid == pid
        assert sum(1 for e in sup.events if e["kind"] == "spawn") == 1
    finally:
        sup.stop()


def test_supervisor_real_empty_view_keeps_spawn_grace():
    """An EMPTY membership dict is a real view (coordinator reachable,
    nobody registered): the never-heartbeated spawn grace stays armed
    and a worker wedged during startup is killed and counted — and
    because the spawn grace is subtracted as detection lag, the wedge
    loop reads as RAPID under the DEFAULT min_uptime_s and the worker
    is abandoned instead of being respawned forever."""
    coord = Coordinator(heartbeat_timeout_s=30)
    argv = [sys.executable, "-c", "import time; time.sleep(30)"]
    sup = Supervisor(lambda wid: argv, ["w0"], coordinator=coord,
                     spawn_grace_s=0.05, restart_max=1)
    sup.start()
    try:
        assert _poll_until(
            sup, lambda: sup.handles["w0"].abandoned, timeout_s=10.0
        ), sup.handles["w0"].summary()
        assert sup.handles["w0"].hang_kills >= 1
    finally:
        sup.stop()


# ---------------------------------------------------------------------------
# end-to-end recovery
# ---------------------------------------------------------------------------


def _start_service(tmp_path, n_shards, **coord_kw):
    coord = Coordinator(**coord_kw)
    coord.set_dataset(list(range(n_shards)))
    server = CoordinatorServer(coord).start()
    return coord, server


def _job_env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_FAULT", None)
    env.update(extra or {})
    return env


def _worker_paths(tmp_path, wid):
    return (str(tmp_path / ("out_%s.json" % wid)),
            str(tmp_path / ("ckpt_%s" % wid)))


def _argv_for(tmp_path, addr):
    def argv(wid):
        out, ck = _worker_paths(tmp_path, wid)
        return [sys.executable, WORKER_PY, out, ck, addr]
    return argv


def _after(tmp_path, victim, step):
    """SUP_START_AFTER for a drill's healthy peers: take no lease
    before the victim has checkpointed `step` tasks. The three
    processes come up in any order and seconds apart under load, and
    two peers drain a dozen 50 ms shards in well under a second: a
    victim that came up last found the queue empty, met its fault
    with nothing accumulated, and resumed from None (the hang drill
    failed so about one run in two beside a loaded suite)."""
    return "%s:%d" % (_worker_paths(tmp_path, victim)[1], step)


def _read_out(tmp_path, wid):
    out, _ = _worker_paths(tmp_path, wid)
    with open(out) as f:
        return json.load(f)


def _run_baseline(tmp_path, n_shards):
    """The uninterrupted oracle: ONE worker, no faults, same shards."""
    coord, server = _start_service(tmp_path, n_shards, timeout_s=30)
    try:
        out = str(tmp_path / "baseline.json")
        ck = str(tmp_path / "baseline_ckpt")
        proc = subprocess.run(
            [sys.executable, WORKER_PY, out, ck, server.address],
            env=_job_env({"PADDLE_WORKER_ID": "baseline",
                          "SUP_TASK_SLEEP": "0"}),
            timeout=300,
        )
        assert proc.returncode == 0
        rec = json.load(open(out))
        assert sorted(rec["history"]) == list(range(n_shards))
        return np.asarray(rec["acc"], dtype=np.float64)
    finally:
        server.stop()


def _union_histories(recs):
    hist = []
    for r in recs:
        hist.extend(r["history"])
    return hist


def _eval_loss(acc):
    """MSE of the job-level final parameters (anchor - accumulated
    update) on a held-out batch — the worker's model is y ~ x @ w."""
    sys.path.insert(0, os.path.dirname(__file__))
    import supervisor_worker as sw

    w = sw.anchor_w().astype(np.float64) - np.asarray(acc).reshape(-1, 1)
    rng = np.random.RandomState(999)
    x = rng.randn(64, sw.FEATURES)
    y = x.sum(axis=1, keepdims=True)
    return float(np.mean((x @ w - y) ** 2))


def test_supervisor_kill_recovery_exact(tmp_path):
    """kill@3 preempts 1 of 3 supervised workers at a step boundary; the
    supervisor restarts it, it resumes at EXACTLY the checkpointed step,
    every shard is processed exactly once across the fleet, and the
    job-level accumulated parameters match an uninterrupted baseline."""
    n_shards = 24
    baseline_acc = _run_baseline(tmp_path, n_shards)

    coord, server = _start_service(
        tmp_path, n_shards, timeout_s=5, failure_max=10,
        heartbeat_timeout_s=30,
    )
    victim = "w0"

    def env_for(wid):
        extra = {"SUP_TASK_SLEEP": "0.05"}
        if wid == victim:
            extra["PADDLE_FAULT"] = "kill@3"  # boundary-preempt: 2 tasks in
        else:
            extra["SUP_START_AFTER"] = _after(tmp_path, victim, 2)
        return _job_env(extra)

    sup = Supervisor(
        _argv_for(tmp_path, server.address), ["w0", "w1", "w2"],
        env_for=env_for, coordinator=coord,
        ckpt_dir_for=lambda wid: _worker_paths(tmp_path, wid)[1],
    )
    try:
        report = sup.run(deadline_s=240)
    finally:
        server.stop()

    assert report["ok"], report
    w = report["workers"]
    assert w[victim]["restarts"] == 1
    assert w[victim]["exit_codes"][0] == -signal.SIGKILL
    assert not any(info["abandoned"] for info in w.values())

    recs = [_read_out(tmp_path, wid) for wid in ("w0", "w1", "w2")]
    vic = recs[0]
    # exact step continuity: kill@3 fired at the start of iteration 3,
    # so exactly 2 tasks were accumulated+checkpointed — the restarted
    # incarnation must resume from precisely there
    assert vic["resumed_from"] == 2, vic
    assert vic["restart_count"] == 1

    # no repeated or skipped task leases, job-wide
    hist = _union_histories(recs)
    assert sorted(hist) == list(range(n_shards)), hist
    assert len(coord.done) == n_shards
    assert not coord.todo and not coord.pending and not coord.discarded

    # final parameters match the uninterrupted run (summation order is
    # the only difference -> float64 accumulators agree to ~1e-15 rel)
    total = np.zeros_like(baseline_acc)
    for r in recs:
        total += np.asarray(r["acc"], dtype=np.float64)
    np.testing.assert_allclose(total, baseline_acc, rtol=1e-9, atol=0)
    # ... and so does the job's final loss on a held-out batch
    np.testing.assert_allclose(
        _eval_loss(total), _eval_loss(baseline_acc), rtol=1e-9
    )

    # crash-loop disk GC: per-step saves with keep_last=2 + supervisor
    # retain() leave a bounded number of step dirs behind
    for wid in ("w0", "w1", "w2"):
        _, ck = _worker_paths(tmp_path, wid)
        assert len(ckpt._list_step_dirs(ck)) <= 2


def test_supervisor_hang_detected_and_recovered(tmp_path):
    """hang@2 livelocks the victim (process alive, no heartbeats): only
    the heartbeat deadline can see it. The supervisor must SIGKILL and
    restart it, and the job must still drain exactly once."""
    n_shards = 12
    coord, server = _start_service(
        tmp_path, n_shards, timeout_s=5, failure_max=10,
        heartbeat_timeout_s=2.0,
    )
    victim = "w0"

    def env_for(wid):
        extra = {"SUP_TASK_SLEEP": "0.05"}
        if wid == victim:
            extra["PADDLE_FAULT"] = "hang@2"  # 1 task in, then livelock
        else:
            extra["SUP_START_AFTER"] = _after(tmp_path, victim, 1)
        return _job_env(extra)

    sup = Supervisor(
        _argv_for(tmp_path, server.address), ["w0", "w1", "w2"],
        env_for=env_for, coordinator=coord,
    )
    try:
        report = sup.run(deadline_s=240)
    finally:
        server.stop()

    assert report["ok"], report
    w = report["workers"]
    assert w[victim]["hang_kills"] == 1
    assert w[victim]["restarts"] == 1
    assert any(e["kind"] == "hang_kill" and e["worker"] == victim
               for e in report["events"])

    recs = [_read_out(tmp_path, wid) for wid in ("w0", "w1", "w2")]
    assert recs[0]["resumed_from"] == 1  # hang fired on iteration 2
    hist = _union_histories(recs)
    assert sorted(hist) == list(range(n_shards)), hist
    assert len(coord.done) == n_shards


def test_supervisor_crashloop_abandons_but_job_drains(tmp_path):
    """A worker that dies mid-lease on the same shard every incarnation
    is a crash loop: after restart_max rapid failures the supervisor
    abandons it, the poisoned shard's lease times out and requeues, and
    the surviving workers drain the whole queue — graceful degradation,
    not a wedged job."""
    n_shards = 10
    coord, server = _start_service(
        tmp_path, n_shards, timeout_s=1.5, failure_max=10,
        heartbeat_timeout_s=30,
    )
    victim = "w0"

    def env_for(wid):
        # survivors keep polling the empty queue long enough to catch
        # the final crash's lease timing out and requeueing
        extra = {"SUP_TASK_SLEEP": "0.05", "SUP_IDLE_GRACE_S": "10.0"}
        if wid == victim:
            # die at the first step boundary of EVERY incarnation —
            # mid-lease whenever the queue still has work
            extra["SUP_CRASH_ON"] = "-1"
        return _job_env(extra)

    sup = Supervisor(
        _argv_for(tmp_path, server.address), ["w0", "w1", "w2"],
        env_for=env_for, coordinator=coord,
        restart_max=2, min_uptime_s=1e9,  # every death counts as rapid
    )
    try:
        report = sup.run(deadline_s=240)
    finally:
        server.stop()

    w = report["workers"]
    assert w[victim]["abandoned"], report
    assert w[victim]["restarts"] == 1  # spawned twice, then given up on
    assert not report["ok"] and not report["timed_out"]
    assert w["w1"]["done"] and w["w2"]["done"]

    # the job still drained EVERYTHING, poisoned shard included
    assert len(coord.done) == n_shards
    assert not coord.todo and not coord.pending and not coord.discarded

    # exactly-once accounting survives the abandonment: the victim's
    # completed shards live on in its (durable) checkpoint history
    hist = _union_histories(
        [_read_out(tmp_path, wid) for wid in ("w1", "w2")]
    )
    _, vic_ck = _worker_paths(tmp_path, victim)
    if ckpt.latest_step(vic_ck) is not None:
        import paddle_tpu.fluid as fluid

        meta = ckpt.load_checkpoint(fluid.executor.Scope(), vic_ck)
        hist.extend(meta["extra"]["history"])
    assert sorted(hist) == list(range(n_shards)), hist


@pytest.mark.slow
def test_supervisor_netsplit_and_kill_combined(tmp_path):
    """The longest drill: one worker rides out an injected 1.5 s
    coordinator partition purely on client backoff (no restart), while
    another is SIGKILLed and restarted — simultaneously. The job must
    drain exactly once and match the uninterrupted baseline."""
    n_shards = 30
    baseline_acc = _run_baseline(tmp_path, n_shards)

    coord, server = _start_service(
        tmp_path, n_shards, timeout_s=5, failure_max=10,
        heartbeat_timeout_s=10.0,  # longer than the partition: no kill
    )

    def env_for(wid):
        extra = {"SUP_TASK_SLEEP": "0.1"}
        if wid == "w0":
            extra["PADDLE_FAULT"] = "netsplit@2:1.5"
        elif wid == "w1":
            extra["PADDLE_FAULT"] = "kill@4"
        return _job_env(extra)

    sup = Supervisor(
        _argv_for(tmp_path, server.address), ["w0", "w1", "w2"],
        env_for=env_for, coordinator=coord,
        ckpt_dir_for=lambda wid: _worker_paths(tmp_path, wid)[1],
    )
    try:
        report = sup.run(deadline_s=300)
    finally:
        server.stop()

    assert report["ok"], report
    w = report["workers"]
    assert w["w0"]["restarts"] == 0  # partition healed by backoff alone
    assert w["w1"]["restarts"] == 1
    recs = [_read_out(tmp_path, wid) for wid in ("w0", "w1", "w2")]
    assert recs[1]["resumed_from"] == 3
    hist = _union_histories(recs)
    assert sorted(hist) == list(range(n_shards)), hist
    total = np.zeros_like(baseline_acc)
    for r in recs:
        total += np.asarray(r["acc"], dtype=np.float64)
    np.testing.assert_allclose(total, baseline_acc, rtol=1e-9, atol=0)
