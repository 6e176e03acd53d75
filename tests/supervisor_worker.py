"""Supervised elastic-training worker (driven by tests/test_supervisor.py).

One logical "job": N of these workers drain a coordinator task queue
where each task is one data shard of a single large-batch SGD step.
Every worker computes the shard's gradient with the REAL fluid machinery
(append_backward -> fused jax.vjp) at a fixed anchor parameter value and
folds `lr * grad` into a float64 accumulator kept in its elastic
checkpoint — so the job-level result, `anchor - sum(all workers' accs)`,
is exact and assignment-independent: it must match an uninterrupted
baseline run NO MATTER which worker processed which shard, how often
workers crashed, hung, or were restarted.

Protocol per iteration (the fault injector ticks at the step boundary,
so injected kill/hang/netsplit land between leases, where recovery must
be exact):

    tick -> heartbeat -> lease -> grad -> accumulate ->
    checkpoint (atomic; history rides in `extra`) -> task_finished

Exactly-once guard: a crash after the checkpoint commit but before
task_finished would double-count on requeue, so the commit records the
just-accumulated task id as `pending_ack` and losing the race the other
way (finished but not checkpointed) is impossible by construction. The
resumed incarnation (a) re-acks `pending_ack` first (idempotent no-op if
the ack landed), and (b) if the lease already timed out and the shard
came back to it, sees the payload in `history` and acks WITHOUT
re-accumulating. Residual window: the lease expires before the victim
resumes AND a peer re-leases the shard — closing that needs the ack and
the state commit to be one transaction (coordinator-side), which the
real pserver does with etcd; here the supervisor restart latency is well
under the lease timeout.
[Crash-loop fixture: SUP_CRASH_ON=<payload> hard-exits mid-lease — before
accumulating — in EVERY incarnation, so the lease times out, requeues,
and exactly-once accounting still holds.]

Usage: supervisor_worker.py OUT_JSON CKPT_DIR COORD_ADDR
Env:   PADDLE_WORKER_ID    logical id (set by the Supervisor)
       PADDLE_FAULT        injected faults (stripped on restart)
       SUP_CRASH_ON        payload int: os._exit(9) mid-lease, every time
                           (-1 = die at the first step boundary of every
                           incarnation, mid-lease when a task was held)
       SUP_TASK_SLEEP      extra seconds per task (paces the queue drain)
       SUP_IDLE_GRACE_S    keep polling an empty queue this long before
                           exiting 0 (covers a dead peer's lease timeout)
       SUP_START_AFTER     "<ckpt dir>:<step>": do not register (so take
                           no lease) before that directory holds a
                           checkpoint of at least that step — a drill's
                           healthy peers wait for the victim's first
                           tasks, whichever process came up first
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import (
    RemoteCoordinator,
    checkpoint as ckpt,
    fault_injection as fi,
)

LR = 0.05
BATCH = 8
FEATURES = 4


def batch_for(payload):
    rng = np.random.RandomState(1234 + int(payload))
    x = rng.randn(BATCH, FEATURES).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True)
         + 0.1 * rng.randn(BATCH, 1)).astype(np.float32)
    return x, y


def anchor_w():
    return np.linspace(-0.5, 0.5, FEATURES).reshape(
        FEATURES, 1).astype(np.float32)


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATURES], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(
            input=x, size=1, bias_attr=False,
            param_attr=fluid.ParamAttr(name="sup_w"),
        )
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=y)
        )
        params_grads = fluid.append_backward(loss)
    (grad_var,) = [g for p, g in params_grads if p.name == "sup_w"]
    return main, startup, loss, grad_var


def main():
    out_path, ckpt_dir, addr = sys.argv[1:4]
    wid = os.environ.get("PADDLE_WORKER_ID", "w?")
    crash_on = os.environ.get("SUP_CRASH_ON")
    crash_on = int(crash_on) if crash_on else None
    task_sleep = float(os.environ.get("SUP_TASK_SLEEP", "0.02"))
    idle_grace = float(os.environ.get("SUP_IDLE_GRACE_S", "1.0"))

    main_p, startup, loss, grad_var = build()
    scope = fluid.Scope()
    injector = fi.default_injector()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set("sup_w", anchor_w())  # fixed anchor: grads are per-shard
        # pay trace+compile BEFORE announcing liveness, so the heartbeat
        # cadence the supervisor sees is the steady-state one
        xw, yw = batch_for(0)
        exe.run(main_p, feed={"x": xw, "y": yw}, fetch_list=[grad_var])

        start_after = os.environ.get("SUP_START_AFTER")
        if start_after:
            # before registering: an unregistered process is under the
            # supervisor's spawn grace, not its heartbeat deadline
            gate_dir, _, gate_step = start_after.rpartition(":")
            while not any(s >= int(gate_step)
                          for s, _ in ckpt._list_step_dirs(gate_dir)):
                time.sleep(0.05)

        client = RemoteCoordinator(addr, retry_deadline_s=20.0,
                                   backoff_base_s=0.05)
        client.register_worker(wid)

        # crash recovery is ONE call: either restore acc+history+step or
        # start from zero
        ckpt_scope = fluid.Scope()
        meta = ckpt.resume_or_init(ckpt_scope, ckpt_dir)
        if meta is not None:
            resumed_from = step = int(meta["extra"]["step"])
            history = list(meta["extra"]["history"])
            acc = np.asarray(ckpt_scope.get("acc_w"), dtype=np.float64)
            pending_ack = meta["extra"].get("pending_ack")
            if pending_ack is not None:
                # the previous incarnation may have died between its
                # checkpoint commit and task_finished: ack now, before
                # the lease times out and requeues an accumulated shard
                # (idempotent no-op if the ack already landed)
                client.task_finished(int(pending_ack))
        else:
            resumed_from = None
            step = 0
            history = []
            acc = np.zeros((FEATURES, 1), np.float64)

        idle_since = None
        while True:
            injector.tick()
            client.heartbeat(wid, step=step)
            task = client.get_task()
            if crash_on == -1:
                os._exit(9)  # crash loop: die leased or not, every time
            if task is None:
                # an empty queue is not a finished job while a dead
                # peer's lease can still time out and requeue its shard
                if idle_since is None:
                    idle_since = time.monotonic()
                if time.monotonic() - idle_since > idle_grace:
                    break
                time.sleep(0.1)
                continue
            idle_since = None
            payload = int(task.payload)
            if crash_on is not None and payload == crash_on:
                os._exit(9)  # preempted MID-LEASE; server timeout requeues
            if payload in history:
                # accumulated by a previous incarnation whose ack was
                # lost and whose lease timed out back to us: ack only
                client.task_finished(task.task_id)
                continue
            if task_sleep:
                time.sleep(task_sleep)
            xd, yd = batch_for(payload)
            (g,) = exe.run(main_p, feed={"x": xd, "y": yd},
                           fetch_list=[grad_var])
            acc = acc + LR * np.asarray(g, dtype=np.float64)
            step += 1
            history.append(payload)
            ckpt_scope.set("acc_w", acc)
            ckpt.save_checkpoint(
                ckpt_scope, ckpt_dir, step=step,
                extra={"step": step, "history": history, "worker": wid,
                       "pending_ack": task.task_id},
                keep_last=2,
            )
            client.task_finished(task.task_id)
        client.heartbeat(wid, step=step)
        client.close()

    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({
            "worker": wid,
            "resumed_from": resumed_from,
            "steps_done": step,
            "history": history,
            "acc": acc.ravel().tolist(),
            "restart_count": int(os.environ.get("PADDLE_RESTART_COUNT", "0")),
        }, f)
    os.replace(tmp, out_path)


if __name__ == "__main__":
    main()
