#!/usr/bin/env python
"""Elastic-training CI gate: preemption tolerance proven with real
process boundaries and a bitwise acceptance bar.

One parent process runs the ElasticCoordinator three times over the
deterministic ci_job (2 logical shards, 32 global steps, 2 epochs);
workers are REAL subprocesses (`python -m mxnet_tpu.elastic.agent`)
writing per-step consumed-example logs.

Gates:

1. reference — a single uninterrupted worker trains to completion;
   its final params are the bitwise yardstick for everything below.
2. SIGKILL mid-epoch — two workers; one carries
   MXNET_TPU_FAULT_INJECT="kill:step:6" and is SIGKILLed by its own
   fault injector mid-epoch (returncode -9, no Python teardown). The
   survivor absorbs the dead rank's logical shard through a shrink
   transition and finishes with final params np.array_equal to the
   reference. The union of both consumed logs covers every (epoch,
   shard, step) batch EXACTLY once with the exact ground-truth
   indices — nothing dropped, nothing double-seen.
3. re-grow 1→2 — a second worker joins mid-run; zero example loss
   (same exactly-once audit), both workers exit "complete", and no
   member retraces after its own warmup step (the joiner bootstraps
   from coordinator state, never a recompile).

elasticStats must agree: one shrink / one grow transition, moved
reshard bytes strictly below the restore-everyone baseline, re-keyed
examples counted, zero cross-worker digest mismatches.
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ENTRY = "mxnet_tpu.elastic.ci_job:build"
KILL_STEP = 6          # victim dies after completing global step 5
TIMEOUT = 600


def _worker(port, name, log, extra_env=None, config=None,
            ready=None, gate=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(extra_env or {})
    argv = [sys.executable, "-m", "mxnet_tpu.elastic.agent",
            "--connect", f"127.0.0.1:{port}", "--entry", ENTRY,
            "--name", name, "--consumed-log", log,
            "--config", json.dumps(config or {})]
    if ready:
        argv += ["--ready-file", ready]
    if gate:
        argv += ["--start-gate", gate]
    return subprocess.Popen(
        argv, env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _read_log(path):
    rows = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                rows.append(json.loads(line))
    return rows


def _audit_exactly_once(check, tag, logs, spec):
    """Every (epoch, shard, step) batch consumed exactly once across
    all logs, with the exact ground-truth sample indices."""
    from mxnet_tpu.data.sampler import epoch_permutation

    seen = {}
    dup = []
    for rows in logs:
        for r in rows:
            key = (r["epoch"], r["shard"], r["step"])
            if key in seen:
                dup.append(key)
            seen[key] = r["idx"]
    S, bpe = spec.logical_shards, spec.batches_per_epoch
    bs = spec.batch_size
    want = {(e, s, p) for e in range(spec.epochs)
            for s in range(S) for p in range(bpe)}
    check(f"{tag}: no batch consumed twice", not dup,
          f"dups={dup[:4]}")
    missing = want - set(seen)
    extra = set(seen) - want
    check(f"{tag}: every batch consumed exactly once",
          not missing and not extra,
          f"missing={sorted(missing)[:4]} extra={sorted(extra)[:4]}")
    bad = []
    for (e, s, p), idx in seen.items():
        perm = epoch_permutation(spec.seed, e, spec.num_samples)
        lo = s * (spec.num_samples // S) + p * bs
        if list(map(int, perm[lo:lo + bs])) != list(map(int, idx)):
            bad.append((e, s, p))
    check(f"{tag}: consumed indices match the Philox ground truth",
          not bad, f"bad={bad[:4]}")


def _no_steady_state_retraces(check, tag, rows, first_step):
    """A member may trace only around its own warmup (its first
    participating step); afterwards the compiled step program is
    reused forever."""
    for row in rows:
        if row["state"] != "active":
            continue
        late = [e for e in row["trace_history"]
                if e[0] > first_step.get(row["wid"], 0) + 1]
        check(f"{tag}: {row['wid']} zero steady-state retraces",
              not late, f"late_traces={late}")


def main():
    failures = []

    def check(name, ok, detail=""):
        print(f"  {'ok  ' if ok else 'FAIL'} {name}"
              + (f" ({detail})" if detail else ""))
        if not ok:
            failures.append(name)

    import numpy as np

    from mxnet_tpu.elastic import ElasticCoordinator, load_entry
    from mxnet_tpu.elastic.stats import elastic_stats

    spec = load_entry(ENTRY)({})
    work = tempfile.mkdtemp(prefix="mx_elastic_gate_")

    # ------------------------------------------------- 1. reference
    print("elastic gate: uninterrupted reference run")
    ref_log = os.path.join(work, "ref.jsonl")
    coord = ElasticCoordinator(
        ENTRY, {}, name="gate_ref", initial_world=1,
        workdir=os.path.join(work, "ref")).start()
    proc = _worker(coord.port, "ref-w0", ref_log)
    ok = coord.wait(TIMEOUT)
    check("reference run completes", ok, coord.status()["phase"])
    ref = coord.final_params()
    coord.stop()
    out, err = proc.communicate(timeout=60)
    check("reference worker exits complete",
          proc.returncode == 0 and '"complete"' in out,
          f"rc={proc.returncode} out={out!r} err={err[-200:]!r}")
    _audit_exactly_once(check, "reference", [_read_log(ref_log)],
                        spec)

    # -------------------------------------- 2. SIGKILL mid-epoch
    print("elastic gate: SIGKILL one of two workers mid-epoch")
    kill_dir = os.path.join(work, "kill")
    logs = [os.path.join(work, f"kill-w{i}.jsonl") for i in range(2)]
    coord = ElasticCoordinator(
        ENTRY, {}, name="gate_kill", initial_world=2,
        workdir=kill_dir).start()
    survivor = _worker(coord.port, "kill-w0", logs[0])
    victim = _worker(
        coord.port, "kill-w1", logs[1],
        extra_env={"MXNET_TPU_FAULT_INJECT": f"kill:step:{KILL_STEP}"})
    vrc = victim.wait(timeout=TIMEOUT)
    check("victim SIGKILLed by its own fault injector",
          vrc == -signal.SIGKILL, f"rc={vrc}")
    ok = coord.wait(TIMEOUT)
    check("survivor finishes the job across the shrink", ok,
          coord.status()["phase"])
    rows = coord.status()["members"]
    got = coord.final_params()
    snap = elastic_stats()["gate_kill"]
    coord.stop()
    out, err = survivor.communicate(timeout=60)
    check("survivor exits complete",
          survivor.returncode == 0 and '"complete"' in out,
          f"rc={survivor.returncode} err={err[-200:]!r}")
    check("final params bitwise equal to the reference",
          all(np.array_equal(ref[n], got[n]) for n in ref),
          str([n for n in ref
               if not np.array_equal(ref[n], got[n])]))
    _audit_exactly_once(check, "kill", [_read_log(p) for p in logs],
                        spec)
    check("exactly one shrink transition",
          snap["transitions_shrink"] == 1
          and snap["transitions_grow"] == 0,
          f"{snap['transitions_shrink']}/{snap['transitions_grow']}")
    check("reshard moved less than a full restore",
          0 < snap["reshard_bytes_moved"]
          < snap["reshard_bytes_full_restore"],
          f"{snap['reshard_bytes_moved']} vs "
          f"{snap['reshard_bytes_full_restore']}")
    check("re-keyed examples counted",
          snap["examples_rekeyed"] > 0, str(snap["examples_rekeyed"]))
    check("zero cross-worker digest mismatches",
          snap["digest_mismatches"] == 0,
          str(snap["digest_mismatches"]))
    meta_path = os.path.join(kill_dir, "transition-g002",
                             "meta.json")
    check("transition checkpoint persisted",
          os.path.exists(meta_path), meta_path)
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        check("transition checkpoint carries per-param specs",
              meta["format"] == "mxnet_tpu/elastic_transition_v1"
              and sorted(meta["sharding"]) == sorted(ref),
              str(sorted(meta.get("sharding", {}))))
    _no_steady_state_retraces(check, "kill", rows,
                              {r["wid"]: 0 for r in rows})

    # ------------------------------------------------ 3. re-grow 1→2
    # The joiner's interpreter takes seconds to warm while the job
    # steps at >100/s, so the leg uses the agent's ready/start-gate
    # pair: both workers warm up FIRST, then w0 is released, and the
    # joiner is released mid-run at a chosen step. A longer job
    # (epochs=12, 192 steps) gives the join runway; its reference is
    # an in-process run of the same config.
    print("elastic gate: grow 1 -> 2 mid-run")
    grow_cfg = {"epochs": 12}
    gspec = load_entry(ENTRY)(grow_cfg)
    gref_log = os.path.join(work, "grow-ref.jsonl")
    coord = ElasticCoordinator(
        ENTRY, grow_cfg, name="gate_grow_ref",
        initial_world=1).start()
    proc = _worker(coord.port, "grow-ref", gref_log,
                   config=grow_cfg)
    ok = coord.wait(TIMEOUT)
    check("grow reference run completes", ok,
          coord.status()["phase"])
    gref = coord.final_params()
    coord.stop()
    proc.communicate(timeout=60)

    logs = [os.path.join(work, f"grow-w{i}.jsonl") for i in range(2)]
    coord = ElasticCoordinator(
        ENTRY, grow_cfg, name="gate_grow", initial_world=1,
        workdir=os.path.join(work, "grow")).start()
    readies = [os.path.join(work, f"grow-ready{i}") for i in range(2)]
    gates = [os.path.join(work, f"grow-go{i}") for i in range(2)]
    w0 = _worker(coord.port, "grow-w0", logs[0], config=grow_cfg,
                 ready=readies[0], gate=gates[0])
    w1 = _worker(coord.port, "grow-w1", logs[1], config=grow_cfg,
                 ready=readies[1], gate=gates[1])
    deadline = time.monotonic() + TIMEOUT
    while (not all(os.path.exists(r) for r in readies)
           and time.monotonic() < deadline):
        time.sleep(0.05)
    check("both grow workers warmed up",
          all(os.path.exists(r) for r in readies))
    open(gates[0], "w").close()          # release w0: world forms
    while (coord.status()["step"] < 5
           and time.monotonic() < deadline):
        time.sleep(0.01)
    join_step = coord.status()["step"]
    check("grow leg reached mid-run before the join",
          5 <= join_step < gspec.total_steps // 2, str(join_step))
    open(gates[1], "w").close()          # release the joiner
    ok = coord.wait(TIMEOUT)
    check("grown job completes", ok, coord.status()["phase"])
    rows = coord.status()["members"]
    got = coord.final_params()
    snap = elastic_stats()["gate_grow"]
    coord.stop()
    for tag, proc in (("w0", w0), ("w1", w1)):
        out, err = proc.communicate(timeout=60)
        check(f"grow {tag} exits complete",
              proc.returncode == 0 and '"complete"' in out,
              f"rc={proc.returncode} err={err[-200:]!r}")
    check("grown final params bitwise equal to the reference",
          all(np.array_equal(gref[n], got[n]) for n in gref),
          str([n for n in gref
               if not np.array_equal(gref[n], got[n])]))
    _audit_exactly_once(check, "grow", [_read_log(p) for p in logs],
                        gspec)
    check("exactly one grow transition",
          snap["transitions_grow"] == 1
          and snap["transitions_shrink"] == 0,
          f"{snap['transitions_grow']}/{snap['transitions_shrink']}")
    check("zero digest mismatches across the grow",
          snap["digest_mismatches"] == 0,
          str(snap["digest_mismatches"]))
    first = {r["wid"]: 0 for r in rows}
    joiner = max(r["wid"] for r in rows)
    first[joiner] = join_step
    _no_steady_state_retraces(check, "grow", rows, first)

    if failures:
        print(f"elastic gate: FAIL — {', '.join(failures)}")
        return 1
    print("elastic gate: OK — SIGKILL mid-epoch and 1→2 re-grow both "
          "finish bitwise equal to the uninterrupted run with every "
          "example consumed exactly once")
    return 0


if __name__ == "__main__":
    sys.exit(main())
