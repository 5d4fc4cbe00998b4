"""Fleet coordinator: lease-and-commit determinism under injected faults.

The acceptance bars of the fault-tolerant fleet PR:

* ``Study.tune(executor="fleet", workers=N)`` reproduces the local async
  executor's suggestions and incumbent **bit-identically** (process and
  socket transports) — remote placement cannot change a decision;
* every injector in :mod:`repro.core.tune_service.faults` (kill / stall /
  drop / dup / delay / hang) leaves the incumbent bit-identical to the
  fault-free run, and two runs under the same fault plan write
  **byte-identical** journals (lease/expire/reissue histories included);
* a unit whose lease expires ``max_attempts`` times is surrendered as a
  FAILED trial — the study finishes, never wedges;
* at zero live workers the coordinator degrades to its local slot;
* a coordinator SIGKILLed mid-run (mid-re-issue included) resumes from
  its journal byte-identically to an uninterrupted twin.

The hardened-fleet PR adds:

* the network-shaped injectors (corrupt / truncate / replay / partition /
  latency) leave the incumbent bit-identical, journal deterministic
  ``reject``/``reconnect`` events, and twin runs stay byte-identical;
* ``scheduler="asha"`` composes with the fleet (both pools): rung
  decisions match the local async ASHA run bitwise, survive the fault
  matrix, and a SIGKILL mid-rung resumes byte-identically;
* a :class:`FleetSpec` + ``tools/fleet_launch.py`` round-trip — CLI
  workers launched from one spec file, auth key via environment — is
  bit-identical to the self-spawned fleet, and the key never reaches the
  journal or argv.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.tune_service import (FaultPlan, FleetExecutor, read_events,
                                     tear_journal)
from repro.core.tune_service.trial import FAILED, TERMINATED

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

SCALE = 0.02
#: common study shape: budget 6 = units 1..6, unit 0 is the default config
KW = dict(budget=6, seed=9, n_init=3)
#: tight heartbeats so silence expiries land in ~1s, not test-timeout land
FLEET_KW = dict(heartbeat_s=0.05, lease_deadline=20)


def _spec(**opts):
    return ExperimentSpec(engine="hemem",
                          workload=WorkloadSpec("gups", scale=SCALE),
                          options=SimOptions(backend="numpy", **opts))


def _histories_equal(a, b):
    return [(o.config, o.value) for o in a.history] == \
        [(o.config, o.value) for o in b.history]


@pytest.fixture(scope="module")
def baseline():
    """The local async twin every fleet run must reproduce bitwise."""
    return Study(_spec()).tune(executor="async", slots=2, **KW)


# ---------------------------------------------------------------------------
# placement invariance: fleet == local async, both transports
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pool", ["process", "socket"])
def test_fleet_matches_async_local(pool, baseline):
    r = Study(_spec()).tune(executor="fleet", workers=2, pool=pool,
                            **KW, **FLEET_KW)
    assert r.best_value == baseline.best_value
    assert r.best.config == baseline.best.config
    assert _histories_equal(r, baseline)
    assert r.trials == baseline.trials
    fs = r.fleet
    assert fs["pool"] == pool and fs["workers"] == 2
    assert fs["n_expired_leases"] == 0 and fs["n_worker_deaths"] == 0
    assert not fs["degraded"]


# ---------------------------------------------------------------------------
# the fault matrix: every injector, journal twins byte-identical
# ---------------------------------------------------------------------------
FAULT_CASES = {
    # injector -> (plan, expected expire reason or None)
    "kill": (FaultPlan(kill=[(2, 0)]), "worker-dead"),
    "stall": (FaultPlan(stall=[(2, 0)]), "expired"),
    "drop": (FaultPlan(drop=[(2, 0)]), "lost"),
    "dup": (FaultPlan(dup=[(2, 0)]), None),
    "delay": (FaultPlan(delay=[(2, 0, 1.5)]), "expired"),
}


@pytest.mark.parametrize("injector", sorted(FAULT_CASES))
def test_fleet_journal_twins_under_fault(injector, baseline, tmp_path):
    plan, reason = FAULT_CASES[injector]
    runs, raws = [], []
    for twin in range(2):
        j = str(tmp_path / f"{injector}{twin}.jsonl")
        r = Study(_spec()).tune(executor="fleet", workers=2, faults=plan,
                                journal=j, **KW, **FLEET_KW)
        runs.append(r)
        raws.append(open(j, "rb").read())
    assert raws[0] == raws[1]
    for r in runs:
        # the fault cost re-execution, never a decision
        assert r.best_value == baseline.best_value
        assert _histories_equal(r, baseline)
        assert r.trials == baseline.trials
    events = read_events(str(tmp_path / f"{injector}0.jsonl"))
    expires = [e for e in events if e["event"] == "expire"]
    reissues = [e for e in events if e["event"] == "reissue"]
    if reason is None:  # dup: the twin is absorbed, no lease ever expires
        assert not expires and not reissues
        assert runs[0].fleet["n_duplicate_results"] >= 1
    else:
        assert [e["reason"] for e in expires] == [reason]
        assert [(e["unit"], e["attempt"]) for e in expires] == [(2, 0)]
        assert [(e["unit"], e["attempt"]) for e in reissues] == [(2, 1)]
    if injector == "kill":
        assert runs[0].fleet["n_worker_deaths"] == 1
        assert runs[0].fleet["n_respawns"] == 1
    # the faulty journal still validates standalone
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import journal_schema
    assert journal_schema.validate_file(
        str(tmp_path / f"{injector}0.jsonl")) == []


def test_fleet_worker_death_promotes_hot_spare(baseline):
    """A process-fleet death refills the slot from the booted hot spare
    (the respawn boot lands on the replacement spare, off the critical
    path) — and the promotion changes nothing the study sees."""
    r = Study(_spec()).tune(executor="fleet", workers=2,
                            faults=FaultPlan(kill=[(2, 0)]),
                            **KW, **FLEET_KW)
    fs = r.fleet
    assert fs["n_worker_deaths"] == 1 and fs["n_respawns"] == 1
    assert fs["n_spare_promotions"] == 1
    assert r.best_value == baseline.best_value
    assert _histories_equal(r, baseline)


def test_fleet_hang_unwedged_by_timeout(baseline, tmp_path):
    # heartbeats keep flowing, the result never comes: only the per-unit
    # timeout can unwedge it, and the bounded trial retry absorbs the loss
    j = str(tmp_path / "hang.jsonl")
    r = Study(_spec()).tune(executor="fleet", workers=2,
                            faults=FaultPlan(hang=[(2, 0)]), timeout_s=0.6,
                            journal=j, **KW, **FLEET_KW)
    assert r.best_value == baseline.best_value
    assert r.n_failed == 0
    retries = [e for e in read_events(j) if e["event"] == "retry"]
    assert len(retries) == 1 and "timeout" in retries[0]["error"]


def test_fleet_surrenders_after_max_attempts(tmp_path):
    # unit 2 loses every result message on every attempt: the lease
    # expires max_attempts (4) times, the unit is surrendered, and with
    # retries=0 the trial fails — the study finishes, never wedges
    plan = FaultPlan(drop=[(2, 0), (2, 1), (2, 2), (2, 3)])
    j = str(tmp_path / "surrender.jsonl")
    r = Study(_spec()).tune(executor="fleet", workers=2, faults=plan,
                            retries=0, journal=j, **KW, **FLEET_KW)
    states = [t["state"] for t in r.trials]
    assert states.count(FAILED) == 1 and states.count(TERMINATED) == 5
    failed = next(t for t in r.trials if t["state"] == FAILED)
    assert "lease expired 4 times" in failed["error"]
    events = read_events(j)
    assert len([e for e in events if e["event"] == "expire"]) == 4
    assert len([e for e in events if e["event"] == "reissue"]) == 3


def test_fleet_degrades_to_local_at_zero_workers(baseline):
    # one worker, killed mid-unit, no respawn budget: every remaining unit
    # runs on the coordinator's local slot — slower, never wedged, and
    # still bit-identical (the unit is a pure function of its coordinates)
    r = Study(_spec()).tune(executor="fleet", workers=1,
                            faults=FaultPlan(kill=[(1, 0)]), max_respawns=0,
                            **KW, **FLEET_KW)
    fs = r.fleet
    assert fs["degraded"] and fs["n_worker_deaths"] == 1
    assert fs["n_respawns"] == 0
    # different study shape than the slots=2 baseline: compare to its own
    # local twin instead
    twin = Study(_spec()).tune(executor="async", slots=1, **KW)
    assert r.best_value == twin.best_value
    assert _histories_equal(r, twin)


# ---------------------------------------------------------------------------
# resume: torn journal, and a SIGKILLed coordinator mid-faulty-run
# ---------------------------------------------------------------------------
def test_fleet_resume_from_torn_journal(tmp_path):
    plan = FaultPlan(kill=[(2, 0)], drop=[(4, 0)])
    kw = dict(executor="fleet", workers=2, faults=plan, **KW, **FLEET_KW)
    j1, j2 = str(tmp_path / "full.jsonl"), str(tmp_path / "torn.jsonl")
    r1 = Study(_spec()).tune(journal=j1, **kw)
    raw = open(j1, "rb").read()
    import shutil
    shutil.copy(j1, j2)
    tear_journal(j2, 9)
    r2 = Study(_spec()).tune(journal=j2, resume=True, **kw)
    assert open(j2, "rb").read() == raw
    assert r2.trials == r1.trials
    assert r2.best_value == r1.best_value
    assert r2.resumed


_KILL_SCRIPT = """
import sys
sys.path.insert(0, {src!r})
from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.tune_service import FaultPlan
spec = ExperimentSpec(engine="hemem",
                      workload=WorkloadSpec("gups", scale={scale!r}),
                      options=SimOptions(backend="numpy"))
Study(spec).tune(budget=24, seed=9, n_init=4, executor="fleet", workers=2,
                 faults=FaultPlan(kill_every=4), max_respawns=24,
                 heartbeat_s=0.05, lease_deadline=20, journal={journal!r})
"""


def test_fleet_coordinator_sigkill_resume_is_byte_identical(tmp_path):
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    kw = dict(budget=24, seed=9, n_init=4, executor="fleet", workers=2,
              faults=FaultPlan(kill_every=4), max_respawns=24, **FLEET_KW)
    j_twin = str(tmp_path / "twin.jsonl")
    r_twin = Study(_spec()).tune(journal=j_twin, **kw)

    j_kill = str(tmp_path / "killed.jsonl")
    proc = subprocess.Popen(
        [sys.executable, "-c",
         _KILL_SCRIPT.format(src=os.path.abspath(src), scale=SCALE,
                             journal=j_kill)])
    try:
        # SIGKILL once the study is past its first injected worker death
        # (unit 4's lease history is journaled at its commit), so the
        # resume replays a re-issue and continues into live ones
        deadline = time.time() + 120
        while time.time() < deadline:
            if os.path.exists(j_kill):
                raw = open(j_kill, "rb").read()
                if raw.count(b'"event": "reissue"') >= 1 and \
                        len(raw.splitlines()) >= 15:
                    break
            time.sleep(0.01)
        else:
            pytest.fail("killed study never journaled a re-issue")
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.wait()
    assert 0 < len(read_events(j_kill)) < len(read_events(j_twin))

    r_res = Study(_spec()).tune(journal=j_kill, resume=True, **kw)
    assert open(j_kill, "rb").read() == open(j_twin, "rb").read()
    assert r_res.trials == r_twin.trials
    assert r_res.best_value == r_twin.best_value
    assert _histories_equal(r_res, r_twin)


# ---------------------------------------------------------------------------
# argument validation
# ---------------------------------------------------------------------------
def test_fleet_rejects_bad_arguments():
    with pytest.raises(ValueError, match="workers"):
        FleetExecutor(workers=0)
    with pytest.raises(ValueError, match="pool"):
        FleetExecutor(workers=1, pool="carrier-pigeon")
    with pytest.raises(ValueError, match="lease_deadline"):
        FleetExecutor(workers=1, lease_deadline=0)
    with pytest.raises(ValueError, match="executor"):
        Study(_spec()).tune(budget=2, workers=2)  # sync path: no fleet knobs


# ---------------------------------------------------------------------------
# network-shaped faults (socket transport): journal twins byte-identical
# ---------------------------------------------------------------------------
NET_FAULT_CASES = {
    # injector -> (plan, journaled reject reason or None)
    "corrupt": (FaultPlan(corrupt=[(2, 0)]), "bad-signature"),
    "truncate": (FaultPlan(truncate=[(2, 0)]), "truncated"),
    # a replayed VALID result: the first copy commits and releases the
    # lease before the replayed copy is even read, so the reject is
    # wall-clock-free stats only — never journaled
    "replay": (FaultPlan(replay=[(2, 0)]), None),
}


@pytest.mark.parametrize("injector", sorted(NET_FAULT_CASES))
def test_socket_fleet_net_fault_journal_twins(injector, baseline, tmp_path):
    plan, reason = NET_FAULT_CASES[injector]
    runs, raws = [], []
    for twin in range(2):
        j = str(tmp_path / f"{injector}{twin}.jsonl")
        r = Study(_spec()).tune(executor="fleet", workers=2, pool="socket",
                                faults=plan, journal=j, **KW, **FLEET_KW)
        runs.append(r)
        raws.append(open(j, "rb").read())
    assert raws[0] == raws[1]
    for r in runs:
        assert r.best_value == baseline.best_value
        assert _histories_equal(r, baseline)
        assert r.trials == baseline.trials
        assert r.fleet["n_rejected_frames"] >= 1
    events = read_events(str(tmp_path / f"{injector}0.jsonl"))
    rejects = [e for e in events if e["event"] == "reject"]
    if reason is None:
        assert not rejects  # stats-only: first commit already won
        assert runs[0].fleet["n_duplicate_results"] == 0
    else:
        assert [(e["unit"], e["attempt"], e["reason"])
                for e in rejects] == [(2, 0, reason)]
        expires = [e for e in events if e["event"] == "expire"]
        assert [(e["unit"], e["reason"]) for e in expires] == [(2, "reject")]
        assert [(e["unit"], e["attempt"]) for e in events
                if e["event"] == "reissue"] == [(2, 1)]
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import journal_schema
    assert journal_schema.validate_file(
        str(tmp_path / f"{injector}0.jsonl")) == []


def test_socket_fleet_reconnect_mid_lease(baseline, tmp_path):
    """A partition mid-lease: the link drops on unit 2's first busy
    heartbeat and the worker re-dials while its evaluation keeps
    computing.  The coordinator re-attaches the live lease (``reconnect``
    journaled at commit), nothing is re-executed, and two partitioned
    runs write byte-identical journals."""
    plan = FaultPlan(partition=[(2, 0, 0.2)])
    raws, runs = [], []
    for twin in range(2):
        j = str(tmp_path / f"part{twin}.jsonl")
        r = Study(_spec()).tune(executor="fleet", workers=2, pool="socket",
                                faults=plan, journal=j, **KW, **FLEET_KW)
        runs.append(r)
        raws.append(open(j, "rb").read())
    assert raws[0] == raws[1]
    for r in runs:
        assert r.best_value == baseline.best_value
        assert _histories_equal(r, baseline)
        assert r.trials == baseline.trials
        assert r.fleet["n_reconnects"] == 1
    events = read_events(str(tmp_path / "part0.jsonl"))
    recon = [e for e in events if e["event"] == "reconnect"]
    assert [(e["unit"], e["attempt"]) for e in recon] == [(2, 0)]
    # the lease survived the gap: no expiry, no re-issue, no duplicate
    assert not [e for e in events if e["event"] in ("expire", "reissue")]
    assert runs[0].fleet["n_duplicate_results"] == 0


def test_socket_fleet_under_injected_latency(baseline):
    """Link latency on every frame (the CI fleet-socket-smoke shape):
    slower, bit-identical."""
    r = Study(_spec()).tune(executor="fleet", workers=2, pool="socket",
                            faults=FaultPlan(net_delay_s=0.005),
                            **KW, **FLEET_KW)
    assert r.best_value == baseline.best_value
    assert _histories_equal(r, baseline)
    assert r.trials == baseline.trials


# ---------------------------------------------------------------------------
# ASHA over fleets: early stopping composes with leases (ROADMAP 3a)
# ---------------------------------------------------------------------------
ASHA_KW = dict(budget=6, seed=9, n_init=3, scheduler="asha")


@pytest.fixture(scope="module")
def asha_baseline():
    return Study(_spec()).tune(executor="async", slots=2, **ASHA_KW)


@pytest.mark.parametrize("pool", ["process", "socket"])
def test_fleet_asha_matches_async_asha(pool, asha_baseline):
    r = Study(_spec()).tune(executor="fleet", workers=2, pool=pool,
                            **ASHA_KW, **FLEET_KW)
    assert r.best_value == asha_baseline.best_value
    assert _histories_equal(r, asha_baseline)
    assert r.trials == asha_baseline.trials
    assert r.epochs_committed == asha_baseline.epochs_committed
    assert r.asha_epochs_saved_frac > 0  # rungs actually stopped trials


def test_fleet_asha_journal_twins_under_faults(asha_baseline, tmp_path):
    # promote/early-stop composes with heartbeat expiry + straggler
    # re-issue: a killed worker and a dropped result mid-rung change
    # re-execution, never a rung decision
    plan = FaultPlan(kill=[(2, 0)], drop=[(4, 0)])
    raws = []
    for twin in range(2):
        j = str(tmp_path / f"asha{twin}.jsonl")
        r = Study(_spec()).tune(executor="fleet", workers=2, faults=plan,
                                journal=j, **ASHA_KW, **FLEET_KW)
        raws.append(open(j, "rb").read())
        assert r.best_value == asha_baseline.best_value
        assert _histories_equal(r, asha_baseline)
        assert r.trials == asha_baseline.trials
    assert raws[0] == raws[1]


_ASHA_KILL_SCRIPT = """
import sys
sys.path.insert(0, {src!r})
from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.tune_service import FaultPlan
spec = ExperimentSpec(engine="hemem",
                      workload=WorkloadSpec("gups", scale={scale!r}),
                      options=SimOptions(backend="numpy"))
Study(spec).tune(budget=16, seed=9, n_init=4, executor="fleet", workers=2,
                 scheduler="asha", faults=FaultPlan(kill_every=6),
                 max_respawns=24, heartbeat_s=0.05, lease_deadline=20,
                 journal={journal!r})
"""


def test_fleet_asha_sigkill_resume_is_byte_identical(tmp_path):
    """SIGKILL the coordinator mid-rung (rung decisions already
    journaled, more to come) and resume: byte-identical to the
    uninterrupted fleet x ASHA twin."""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    kw = dict(budget=16, seed=9, n_init=4, executor="fleet", workers=2,
              scheduler="asha", faults=FaultPlan(kill_every=6),
              max_respawns=24, **FLEET_KW)
    j_twin = str(tmp_path / "twin.jsonl")
    r_twin = Study(_spec()).tune(journal=j_twin, **kw)

    j_kill = str(tmp_path / "killed.jsonl")
    proc = subprocess.Popen(
        [sys.executable, "-c",
         _ASHA_KILL_SCRIPT.format(src=os.path.abspath(src), scale=SCALE,
                                  journal=j_kill)])
    try:
        # kill once at least one rung decision is journaled (mid-rung:
        # more trials are still climbing)
        deadline = time.time() + 120
        while time.time() < deadline:
            if os.path.exists(j_kill):
                raw = open(j_kill, "rb").read()
                if raw.count(b'"event": "rung"') >= 2:
                    break
            time.sleep(0.01)
        else:
            pytest.fail("killed study never journaled a rung decision")
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.wait()
    assert 0 < len(read_events(j_kill)) < len(read_events(j_twin))

    r_res = Study(_spec()).tune(journal=j_kill, resume=True, **kw)
    assert open(j_kill, "rb").read() == open(j_twin, "rb").read()
    assert r_res.trials == r_twin.trials
    assert r_res.best_value == r_twin.best_value
    assert _histories_equal(r_res, r_twin)


# ---------------------------------------------------------------------------
# the deployable fleet: spec-driven launcher + externally-launched workers
# ---------------------------------------------------------------------------
def test_fleet_spec_launcher_roundtrip(baseline, tmp_path):
    """The whole multi-host shape on one box: ``FleetSpec`` written to
    disk, ``tools/fleet_launch.py`` bringing up CLI workers that dial in
    and greet (auth key via environment, never argv), the coordinator
    binding the spec's port — and the study still bit-identical."""
    import socket as socket_mod
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import fleet_launch
    from repro.core.tune_service import FleetSpec

    with socket_mod.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    spec = FleetSpec.generate(workers=2, hosts=("127.0.0.1", "127.0.0.1"),
                              port=port, heartbeat_s=FLEET_KW["heartbeat_s"],
                              lease_deadline=FLEET_KW["lease_deadline"])
    spec_path = str(tmp_path / "fleet.json")
    spec.save(spec_path)

    j = str(tmp_path / "fleet.jsonl")
    with fleet_launch.LocalFleet(spec, spec_path) as fleet:
        # workers re-dial with backoff until the coordinator binds
        r = Study(_spec()).tune(executor="fleet", fleet_spec=spec,
                                journal=j, **KW)
        assert fleet.wait_greeted(timeout_s=30.0)
        fleet.join(10.0)  # the coordinator's shutdown frame ends them
        assert fleet.alive == 0
    assert r.best_value == baseline.best_value
    assert _histories_equal(r, baseline)
    assert r.trials == baseline.trials
    assert not r.fleet["degraded"]
    # the journal never saw the fleet's secret
    assert spec.auth_key.encode() not in open(j, "rb").read()


def test_fleet_launch_init_and_print(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import fleet_launch
    from repro.core.tune_service import FleetSpec

    spec_path = str(tmp_path / "fleet.json")
    assert fleet_launch.main([spec_path, "--init", "--workers", "3",
                              "--hosts", "h1,h2,h3"]) == 0
    assert os.stat(spec_path).st_mode & 0o777 == 0o600
    spec = FleetSpec.load(spec_path)
    assert spec.workers == 3 and spec.external and spec.port != 0
    capsys.readouterr()
    assert fleet_launch.main([spec_path, "--print"]) == 0
    out = capsys.readouterr().out
    # one command per host, keyless argv
    for h in ("h1", "h2", "h3"):
        assert f"{h}$" in out
    assert spec.auth_key not in out


def test_fleet_spec_requires_fleet_executor():
    from repro.core.tune_service import FleetSpec
    with pytest.raises(ValueError, match="fleet_spec"):
        Study(_spec()).tune(budget=2, fleet_spec=FleetSpec.generate())


def test_process_fleet_drops_a_truncated_channel():
    """A process worker killed mid-message leaves a truncated frame on its
    own pipe: the fleet stops reading that pipe and still hears the rest
    (on one shared queue such a death could mute every worker)."""
    import multiprocessing as mp
    import struct

    from repro.core.tune_service.coordinator import _ProcessFleet

    fleet = _ProcessFleet(0, 0.1, FaultPlan(), None, spares=0)
    cut_r, cut_w = mp.Pipe(duplex=False)
    ok_r, ok_w = mp.Pipe(duplex=False)
    fleet._outboxes.update({0: cut_r, 1: ok_r})
    os.write(cut_w.fileno(), struct.pack("!i", 100) + b"abc")
    cut_w.close()
    ok_w.send({"type": "hello", "worker": 1})
    got = [fleet.poll(0.5) for _ in range(3)]
    assert {"type": "hello", "worker": 1} in got
    assert list(fleet._outboxes) == [1]
    ok_w.close()
    fleet.close()
