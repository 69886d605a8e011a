"""Elastic serve fleet (r19): consistent-hash placement stability,
lease expiry → dead-worker recovery on an injectable wall clock,
first-class migration (bitwise vs an unmigrated reference), torn-ship
revert at the ``fleet.migrate`` fault site, coordinator
degrade-never-kill on a poisoned tenant spec, the fleet doctor, the
r19 request-drain race regression, and the fleet-flags drift check.
Everything in-process and steppable — the coordinator and workers are
plain objects with injectable clocks; the REAL multi-process kills
live in scripts/chaos_crash_matrix.py (FLEET_KILL_SITES)."""

import glob
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

import sntc_tpu.resilience as R
from sntc_tpu.core.base import Transformer
from sntc_tpu.core.frame import Frame
from sntc_tpu.obs import reset_registry
from sntc_tpu.serve import MemorySink, MemorySource, ServeDaemon, TenantSpec
from sntc_tpu.serve.fleet import (
    ConsistentHashRing,
    FleetCoordinator,
    FleetWorker,
    fsck_fleet,
    restore_retired,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    R.clear()
    R.clear_events()
    R.reset_breakers()
    yield
    R.clear()
    R.clear_events()
    R.reset_breakers()
    # fleet runs emit many distinct (event, tenant) series into the
    # process-global metrics registry; left behind, they exhaust the
    # 64-label-set cardinality cap for every later test file
    reset_registry()


class _Identity(Transformer):
    def transform(self, frame):
        return frame


class FakeWall:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _frames(n_batches, rows=4, base=0):
    return [
        Frame({"x": np.arange(rows, dtype=np.float64) + 100 * b + base})
        for b in range(n_batches)
    ]


def _specs(n_tenants, batches=3):
    specs, sinks = {}, {}
    for i in range(n_tenants):
        tid = f"t{i}"
        sinks[tid] = MemorySink()
        specs[tid] = TenantSpec(
            tenant_id=tid,
            model=_Identity(),
            source=MemorySource(_frames(batches, base=1000 * i)),
            sink=sinks[tid],
        )
    return specs, sinks


def _fleet(tmp_path, worker_ids, specs, wall, **kw):
    root = str(tmp_path / "fleet")
    coord = FleetCoordinator(root, worker_ids, specs, wall=wall, **kw)
    workers = {
        w: FleetWorker(w, root, specs, wall=wall) for w in worker_ids
    }
    return root, coord, workers


def _step(coord, workers, wall, rounds, dt=0.5):
    for _ in range(rounds):
        wall.t += dt
        for w in workers.values():
            w.tick()
        coord.tick()


def _sink_rows(sink):
    """(batch_id, value-tuple) pairs — the bitwise evidence."""
    return sorted(
        (bid, tuple(np.asarray(f["x"]).tolist()))
        for bid, f in sink.batches
    )


def _tenant_homes(root, tid):
    """Workers whose on-disk tree holds the tenant (single-homed
    invariant: exactly one, shipping partials count as homes)."""
    return sorted(
        p.split(os.sep)[-3] for p in glob.glob(
            os.path.join(root, "worker", "*", "tenant", tid)
        ) + glob.glob(
            os.path.join(root, "worker", "*", "tenant", tid + ".shipping")
        )
    )


# ---------------------------------------------------------------------------
# placement: the consistent-hash ring
# ---------------------------------------------------------------------------


def test_ring_assignment_deterministic_and_bounded_load():
    costs = {f"t{i}": 1.0 + (i % 3) for i in range(60)}
    ring = ConsistentHashRing(["w0", "w1", "w2", "w3"])
    a = ring.assign(costs)
    assert a == ring.assign(costs)  # fully deterministic
    assert set(a) == set(costs)
    cap = ring.capacity(costs)
    load = {}
    for tid, w in a.items():
        load[w] = load.get(w, 0.0) + costs[tid]
    assert all(l <= cap + 1e-9 for l in load.values())
    # every worker carries SOMETHING at 60 tenants / 4 workers
    assert set(load) == {"w0", "w1", "w2", "w3"}


def test_ring_join_leave_moves_a_bounded_share():
    costs = {f"t{i}": 1.0 for i in range(100)}
    before = ConsistentHashRing(["w0", "w1", "w2", "w3"]).assign(costs)
    after_join = ConsistentHashRing(
        ["w0", "w1", "w2", "w3", "w4"]
    ).assign(costs)
    moved = sum(1 for t in costs if before[t] != after_join[t])
    # the consistent-hashing property: a join claims roughly its own
    # share (1/5 here), never a full reshuffle
    assert 0 < moved <= 50
    after_leave = ConsistentHashRing(["w0", "w1", "w2"]).assign(costs)
    relocated = sum(
        1 for t in costs
        if before[t] != "w3" and before[t] != after_leave[t]
    )
    # w3's tenants MUST move; the survivors' mostly stay put
    assert relocated <= 40


def test_ring_pinned_tenant_stays_put():
    costs = {f"t{i}": 1.0 for i in range(20)}
    ring = ConsistentHashRing(["w0", "w1", "w2"])
    a = ring.assign(costs, pinned={"t7": "w2", "t11": "w0"})
    assert a["t7"] == "w2"
    assert a["t11"] == "w0"


# ---------------------------------------------------------------------------
# the fleet loop: bootstrap, lease expiry, recovery, rejoin
# ---------------------------------------------------------------------------


def test_fleet_bootstrap_serves_every_tenant(tmp_path):
    wall = FakeWall()
    specs, sinks = _specs(4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall, lease_ttl_s=5.0
    )
    assert set(coord.assignments) == set(specs)
    _step(coord, workers, wall, 30)
    st = coord.status()
    assert all(w["state"] == "live" for w in st["workers"].values())
    for tid, sink in sinks.items():
        assert len(sink.batches) == 3, tid
        assert _tenant_homes(root, tid) == [
            coord.assignments[tid]["worker"]
        ]
    rep = fsck_fleet(root)
    assert rep["ok"], rep["errors"]
    for w in workers.values():
        w.drain()
        w.close()
    coord.close()


def test_lease_expiry_migrates_tenants_to_survivor(tmp_path):
    wall = FakeWall()
    specs, sinks = _specs(4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall, lease_ttl_s=5.0
    )
    _step(coord, workers, wall, 4)  # everyone live, some rows served
    dead_tenants = [
        t for t, e in coord.assignments.items() if e["worker"] == "w1"
    ]
    assert dead_tenants  # the hash ring spreads 4 tenants over 2
    # w1 stops heartbeating; the injectable wall walks past the TTL
    for _ in range(20):
        wall.t += 1.0
        workers["w0"].tick()
        coord.tick()
    st = coord.status()
    assert st["workers"]["w1"]["state"] == "dead"
    for tid in dead_tenants:
        assert coord.assignments[tid] == {
            "worker": "w0", "phase": "serving",
        }
        assert _tenant_homes(root, tid) == ["w0"]
    # zero committed rows lost: EVERY tenant finishes on the survivor
    _step(coord, workers, wall, 10)
    for tid, sink in sinks.items():
        assert len(sink.batches) == 3, tid
    assert coord.migrations["completed"] >= len(dead_tenants)
    for w in workers.values():
        w.close()
    coord.close()


def test_dead_worker_rejoin_goes_live_again(tmp_path):
    wall = FakeWall()
    specs, _ = _specs(4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall, lease_ttl_s=5.0
    )
    _step(coord, workers, wall, 4)
    for _ in range(15):  # kill w1's heartbeat past the TTL
        wall.t += 1.0
        workers["w0"].tick()
        coord.tick()
    assert coord.status()["workers"]["w1"]["state"] == "dead"
    _step(coord, workers, wall, 20)  # w1 heartbeats again → join
    st = coord.status()
    assert st["workers"]["w1"]["state"] == "live"
    for tid, e in coord.assignments.items():
        assert e["phase"] == "serving", (tid, e)
        assert _tenant_homes(root, tid) == [e["worker"]]
    for w in workers.values():
        w.close()
    coord.close()


# ---------------------------------------------------------------------------
# migration: first-class, bitwise, and safe to tear
# ---------------------------------------------------------------------------


def test_migration_bitwise_vs_unmigrated_reference(tmp_path):
    wall = FakeWall()
    ref_specs, ref_sinks = _specs(4, batches=4)
    _, ref_coord, ref_workers = _fleet(
        tmp_path / "ref", ["w0", "w1"], ref_specs, wall
    )
    _step(ref_coord, ref_workers, wall, 30)

    specs, sinks = _specs(4, batches=4)
    root, coord, workers = _fleet(
        tmp_path / "mig", ["w0", "w1"], specs, wall
    )
    _step(coord, workers, wall, 3)  # mid-stream, rows still flowing
    tid = next(
        t for t, e in coord.assignments.items() if e["worker"] == "w0"
    )
    assert coord.migrate_tenant(tid, reason="rebalance")
    _step(coord, workers, wall, 30)
    assert coord.assignments[tid] == {"worker": "w1", "phase": "serving"}
    assert coord.migrations["completed"] == 1
    assert _tenant_homes(root, tid) == ["w1"]
    # a verified sealed manifest records the move
    manifest = json.load(open(
        os.path.join(root, "fleet", "migrations", f"{tid}.json")
    ))
    assert manifest["tenant"] == tid and manifest["dst"] == "w1"
    # the migrated fleet's sinks are bitwise the unmigrated fleet's
    for t in specs:
        assert _sink_rows(sinks[t]) == _sink_rows(ref_sinks[t]), t
    for w in list(workers.values()) + list(ref_workers.values()):
        w.close()
    coord.close()
    ref_coord.close()


def test_remigration_before_new_owner_applied_releases_ghost(tmp_path):
    """A tenant re-migrated AWAY from a worker before that worker ever
    applied the epoch that gave it the tenant: the named source holds
    nothing and must release immediately (a ``never_held`` marker) —
    not leave the coordinator waiting on a ghost forever."""
    wall = FakeWall()
    specs, sinks = _specs(4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall
    )
    _step(coord, workers, wall, 2)  # both live, serving started
    tid = next(
        t for t, e in coord.assignments.items() if e["worker"] == "w0"
    )
    assert coord.migrate_tenant(tid, "w1", reason="rebalance")
    # only the SOURCE ticks: the flip to serving@w1 completes without
    # w1 ever applying the epoch that hands it the tenant
    for _ in range(20):
        wall.t += 0.5
        workers["w0"].tick()
        coord.tick()
        if coord.assignments[tid] == {"worker": "w1",
                                      "phase": "serving"}:
            break
    assert coord.assignments[tid] == {"worker": "w1", "phase": "serving"}
    # ...and is immediately migrated BACK before w1 ticks once
    assert coord.migrate_tenant(tid, "w0", reason="rebalance")
    _step(coord, workers, wall, 30)
    assert coord.assignments[tid] == {"worker": "w0", "phase": "serving"}
    assert coord.migrations["completed"] == 2
    assert _tenant_homes(root, tid) == ["w0"]
    for t, sink in sinks.items():
        assert len(sink.batches) == 3, t  # zero committed rows lost
    for w in workers.values():
        w.close()
    coord.close()


def test_torn_ship_reverts_to_source_and_loses_nothing(tmp_path):
    wall = FakeWall()
    specs, sinks = _specs(4, batches=4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall
    )
    _step(coord, workers, wall, 3)
    tid = next(
        t for t, e in coord.assignments.items() if e["worker"] == "w0"
    )
    assert coord.migrate_tenant(tid, reason="rebalance")
    R.arm("fleet.migrate", "io", times=1)  # tear the ship mid-copy
    _step(coord, workers, wall, 30)
    # the torn copy quarantined; the tenant re-resumed at the SOURCE
    assert coord.migrations["reverted"] == 1
    assert coord.assignments[tid] == {"worker": "w0", "phase": "serving"}
    assert _tenant_homes(root, tid) == ["w0"]
    for t, sink in sinks.items():
        assert len(sink.batches) == 4, t  # zero committed rows lost
    for w in workers.values():
        w.close()
    coord.close()


def test_poisoned_spec_degrades_tenant_never_kills_worker(tmp_path):
    wall = FakeWall()
    specs, sinks = _specs(3)
    specs["bad"] = TenantSpec(
        tenant_id="bad", model=_Identity(), sink=MemorySink(),
    )  # no source AND no watch dir: raises at build
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall
    )
    _step(coord, workers, wall, 25)
    st = coord.status()
    assert all(w["state"] == "live" for w in st["workers"].values())
    assert coord.assignments["bad"]["phase"] == "failed"
    for tid, sink in sinks.items():  # the healthy tenants all finish
        assert len(sink.batches) == 3, tid
    # parked means parked: further rounds never reassign it
    _step(coord, workers, wall, 5)
    assert coord.assignments["bad"]["phase"] == "failed"
    for w in workers.values():
        w.close()
    coord.close()


# ---------------------------------------------------------------------------
# the review-hardening regressions: fencing, torn request tails,
# stranded drains, ghost controller targets, reserved ids
# ---------------------------------------------------------------------------


def test_worker_id_fleet_is_reserved(tmp_path):
    wall = FakeWall()
    specs, _ = _specs(1)
    with pytest.raises(ValueError, match="reserved"):
        FleetWorker("fleet", str(tmp_path / "r"), specs, wall=wall)
    with pytest.raises(ValueError, match="reserved"):
        FleetCoordinator(
            str(tmp_path / "r"), ["w0", "fleet"], specs, wall=wall
        )
    root, coord, workers = _fleet(tmp_path, ["w0"], specs, wall)
    coord.add_worker("w1")  # a legal join still works
    with pytest.raises(ValueError, match="reserved"):
        coord.add_worker("fleet")
    for w in workers.values():
        w.close()
    coord.close()


def test_heartbeat_thread_renews_lease_without_ticks(tmp_path):
    """The r19 fix for slow-worker false death: the dedicated
    heartbeat thread keeps the lease fresh while the serving thread is
    parked (a minutes-long compile in real life)."""
    specs, _ = _specs(1)
    w = FleetWorker(
        "w0", str(tmp_path / "fleet"), specs,
        heartbeat_interval_s=0.02,
    )
    assert w.start_heartbeat()
    assert not w.start_heartbeat()  # idempotent: one thread only
    try:
        deadline = time.time() + 5.0
        lease_file = os.path.join(
            str(tmp_path / "fleet"), "fleet", "workers", "w0",
            "lease.json",
        )
        seq = -1
        while time.time() < deadline and seq < 3:
            time.sleep(0.01)
            try:
                seq = json.load(open(lease_file))["seq"]
            except (OSError, ValueError):
                pass
        # several renewals landed although tick() never ran
        assert seq >= 3
    finally:
        w.stop_heartbeat()
        w.close()


def test_dead_source_ship_fenced_by_grace_and_lease_recheck(tmp_path):
    """A worker declared dead off a 5s TTL must NOT have its tree
    shipped immediately: the ship waits the extra dead-grace, and a
    lease renewal inside that window aborts the ship entirely —
    split-brain fencing for the slow-but-alive worker."""
    wall = FakeWall()
    specs, sinks = _specs(4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall,
        lease_ttl_s=5.0, dead_grace_s=10.0,
    )
    _step(coord, workers, wall, 4)
    w1_tenants = [
        t for t, e in coord.assignments.items() if e["worker"] == "w1"
    ]
    assert w1_tenants
    # w1 goes silent just past the TTL: declared dead, NOT shipped
    for _ in range(8):
        wall.t += 1.0
        workers["w0"].tick()
        coord.tick()
    assert coord.status()["workers"]["w1"]["state"] == "dead"
    for tid in w1_tenants:
        assert coord.assignments[tid]["phase"] == "draining"
        assert "w1" in _tenant_homes(root, tid)  # tree untouched
    assert coord.migrations["completed"] == 0
    # w1 renews INSIDE the grace window: the ship aborts, the worker
    # revives, and the tenants settle through the normal drain path
    _step(coord, workers, wall, 30)
    st = coord.status()
    assert st["workers"]["w1"]["state"] == "live"
    for tid, e in coord.assignments.items():
        assert e["phase"] == "serving", (tid, e)
        assert _tenant_homes(root, tid) == [e["worker"]]
    for tid, sink in sinks.items():
        assert len(sink.batches) == 3, tid  # zero committed rows lost
    for w in workers.values():
        w.close()
    coord.close()


def test_dead_source_tree_retires_instead_of_rmtree(tmp_path):
    """After a truly-dead source's tenants ship, its trees move to
    fleet/retired/ (evidence preserved for a zombie writer) instead of
    being deleted — and the serving namespace stays single-homed."""
    wall = FakeWall()
    specs, sinks = _specs(4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall,
        lease_ttl_s=5.0, dead_grace_s=4.0,
    )
    _step(coord, workers, wall, 4)
    dead_tenants = [
        t for t, e in coord.assignments.items() if e["worker"] == "w1"
    ]
    for _ in range(20):
        wall.t += 1.0
        workers["w0"].tick()
        coord.tick()
    for tid in dead_tenants:
        assert coord.assignments[tid] == {
            "worker": "w0", "phase": "serving",
        }
        assert _tenant_homes(root, tid) == ["w0"]
        assert glob.glob(os.path.join(
            root, "fleet", "retired", f"{tid}.w1.*"
        )), tid
    _step(coord, workers, wall, 10)
    for tid, sink in sinks.items():
        assert len(sink.batches) == 3, tid
    for w in workers.values():
        w.close()
    coord.close()


def test_retired_trees_fsck_verified_and_restorable(tmp_path):
    """r23: retired dead-source trees are part of the fleet fsck
    surface — verified, not just parked — and ``restore_retired``
    recovers one into an explicit destination with a sealed restore
    manifest.  A retired tree that fails fsck refuses to restore."""
    wall = FakeWall()
    specs, _sinks = _specs(4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall,
        lease_ttl_s=5.0, dead_grace_s=4.0,
    )
    _step(coord, workers, wall, 4)
    for _ in range(20):  # w1 dies; its trees retire
        wall.t += 1.0
        workers["w0"].tick()
        coord.tick()
    retired = sorted(
        os.path.basename(p) for p in
        glob.glob(os.path.join(root, "fleet", "retired", "*"))
    )
    assert retired
    # 1. fsck covers every retired tree
    rep = fsck_fleet(root)
    assert rep["ok"], rep
    assert sorted(rep["retired"]) == retired
    assert all(r["ok"] for r in rep["retired"].values())
    # 2. restore a verified tree into an explicit destination
    dest = str(tmp_path / "restored")
    rr = restore_retired(root, retired[0], dest)
    assert rr["ok"] is True and rr["files"] > 0
    from sntc_tpu.resilience.storage import load_sealed_json

    man = load_sealed_json(os.path.join(dest, "restore_manifest.json"))
    assert man["retired"] == retired[0]
    for rel, size, _sha in man["files"]:
        assert os.path.getsize(os.path.join(dest, rel)) == size
    assert R.recent_events(event="fleet_retired_restored")
    # 3. a missing name refuses cleanly
    miss = restore_retired(root, "nope.w9.0", str(tmp_path / "x"))
    assert miss["ok"] is False and miss["error"] == "no such retired tree"
    # 4. a corrupted retired tree fails fleet fsck AND refuses restore
    victim_dir = os.path.join(root, "fleet", "retired", retired[0])
    victims = glob.glob(
        os.path.join(victim_dir, "**", "commits", "*.json"),
        recursive=True,
    ) or glob.glob(os.path.join(victim_dir, "**", "*.json"),
                   recursive=True)
    with open(victims[0], "w") as f:
        f.write('{"torn": ')
    rep2 = fsck_fleet(root, repair=False)
    assert rep2["ok"] is False
    assert rep2["retired"][retired[0]]["ok"] is False
    rr2 = restore_retired(
        root, retired[0], str(tmp_path / "y"), repair=False,
    )
    assert rr2["ok"] is False and rr2["error"] == "retired tree fails fsck"
    for w in workers.values():
        w.close()
    coord.close()


def test_dead_source_failed_ship_restores_from_warm_replica(tmp_path):
    """r23 tentpole wiring: when a dead worker's tenant tree cannot
    ship (every attempt tears) revert-to-source is impossible — the
    coordinator promotes the tenant's warm-standby replica into the
    destination tree instead of parking the tenant as failed, and the
    tenant finishes its arc with zero committed-row loss."""
    from sntc_tpu.obs.metrics import registry
    from sntc_tpu.resilience.replicate import ReplicationPlane
    from sntc_tpu.serve.fleet import tenant_tree

    wall = FakeWall()
    n_batches = 6
    specs, sinks = _specs(4, batches=n_batches)
    standby = str(tmp_path / "standby")
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall,
        lease_ttl_s=5.0, dead_grace_s=4.0, standby_root=standby,
    )
    _step(coord, workers, wall, 4)
    tid = next(
        t for t, e in coord.assignments.items() if e["worker"] == "w1"
    )
    workers["w1"].close()  # dies mid-arc; tree quiescent on disk
    # the warm replica a live ReplicationPlane would have left behind:
    # ship the (still healthy) tree and seal a barrier at its last
    # durable commit, BEFORE the ship path is sabotaged below
    tree = tenant_tree(root, "w1", tid)
    commits = sorted(
        glob.glob(os.path.join(tree, "ckpt", "commits", "*.json"))
    )
    assert commits  # the tenant committed something before death
    with open(commits[-1]) as f:
        last = json.load(f)
    bid = int(os.path.splitext(os.path.basename(commits[-1]))[0])
    plane = ReplicationPlane(tree, standby, tenant=tid)
    plane.on_commit(bid, last, 0)
    plane.close()
    # every ship attempt of THIS tenant tears; the source is dead, so
    # the replica is the only way back
    R.arm(f"tenant/{tid}/fleet.migrate", "io", times=None)
    for _ in range(20):
        wall.t += 1.0
        workers["w0"].tick()
        coord.tick()
    R.disarm(f"tenant/{tid}/fleet.migrate")
    assert coord.assignments[tid] == {"worker": "w0", "phase": "serving"}
    assert os.path.isdir(
        os.path.join(tenant_tree(root, "w0", tid), "ckpt")
    )
    evs = R.recent_events(event="tenant_restored_from_replica")
    assert evs and evs[-1]["tenant"] == tid
    assert (registry().get(
        "sntc_fleet_migrations_total",
        reason="replica_restore", outcome="completed",
    ) or 0) == 1
    # the restored tenant resumes from the barrier and finishes the
    # arc — no committed batch lost, none duplicated
    _step(coord, workers, wall, 15)
    for t, sink in sinks.items():
        assert len(sink.batches) == n_batches, t
    for w in workers.values():
        w.close()
    coord.close()


def test_torn_request_tail_is_not_dropped(tmp_path):
    """A partially-appended fleet request (torn tail, non-ASCII reason
    included) must be consumed on the tick AFTER the line completes —
    these requests fire once per tenant per daemon lifetime, so a
    dropped line is never re-posted."""
    wall = FakeWall()
    specs, _ = _specs(4)
    root, coord, workers = _fleet(tmp_path, ["w0", "w1"], specs, wall)
    _step(coord, workers, wall, 3)
    tid = next(
        t for t, e in coord.assignments.items() if e["worker"] == "w0"
    )
    line = json.dumps({
        "action": "migrate", "tenant": tid,
        "reason": "café-überload",  # non-ASCII: bytes ≠ chars
        "worker": "w0",
    }, ensure_ascii=False).encode()
    path = os.path.join(
        root, "fleet", "workers", "w0", "requests.jsonl"
    )
    with open(path, "ab") as f:  # torn mid-append: no newline yet
        f.write(line[:len(line) // 2])
    coord.tick()
    assert coord.assignments[tid]["phase"] == "serving"  # not consumed
    with open(path, "ab") as f:  # the append completes
        f.write(line[len(line) // 2:] + b"\n")
    coord.tick()
    assert coord.assignments[tid]["phase"] == "draining"
    _step(coord, workers, wall, 20)
    assert coord.assignments[tid]["phase"] == "serving"
    assert coord.migrations["completed"] == 1
    for w in workers.values():
        w.close()
    coord.close()


def test_draining_tenant_reverts_to_source_when_dst_dies(tmp_path):
    """Destination dies mid-migration with no other live worker: the
    draining tenant must revert to its intact source instead of being
    stranded in 'draining' forever."""
    wall = FakeWall()
    specs, sinks = _specs(4, batches=4)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall, lease_ttl_s=5.0
    )
    _step(coord, workers, wall, 3)
    tid = next(
        t for t, e in coord.assignments.items() if e["worker"] == "w0"
    )
    assert coord.migrate_tenant(tid, "w1", reason="rebalance")
    # w1 (the destination) goes silent while the SOURCE is still
    # mid-drain (it heartbeats but never applies the draining epoch,
    # so it never releases) — the classic dst-death-mid-migration
    for _ in range(25):
        wall.t += 1.0
        workers["w0"].renew_lease()
        coord.tick()
    assert coord.status()["workers"]["w1"]["state"] == "dead"
    assert coord.assignments[tid] == {"worker": "w0", "phase": "serving"}
    assert coord.migrations["reverted"] >= 1
    # the source never even noticed: serving resumes there untouched
    _step(coord, {"w0": workers["w0"]}, wall, 30)
    for t, sink in sinks.items():
        assert len(sink.batches) == 4, t  # zero committed rows lost
    for w in workers.values():
        w.close()
    coord.close()


# ---------------------------------------------------------------------------
# the fleet doctor
# ---------------------------------------------------------------------------


def test_fsck_fleet_repairs_torn_journal_flags_broken_seal(tmp_path):
    wall = FakeWall()
    specs, _ = _specs(2)
    root, coord, workers = _fleet(
        tmp_path, ["w0", "w1"], specs, wall
    )
    _step(coord, workers, wall, 10)
    tid = next(
        t for t, e in coord.assignments.items() if e["worker"] == "w0"
    )
    assert coord.migrate_tenant(tid, reason="rebalance")
    _step(coord, workers, wall, 15)
    assert coord.migrations["completed"] == 1
    # tear the assignment journal mid-line (crash mid-append)
    journal = os.path.join(root, "fleet", "assignments.jsonl")
    with open(journal, "a") as f:
        f.write('{"epoch": 99, "torn')
    rep = fsck_fleet(root)
    assert rep["ok"], rep["errors"]
    assert len(rep["repaired"]) >= 1
    records = [
        json.loads(line)
        for line in open(journal) if line.strip()
    ]
    assert all("torn" not in json.dumps(r) for r in records)
    # a broken migration-manifest seal is UNREPAIRABLE: ok=False
    mpath = os.path.join(root, "fleet", "migrations", f"{tid}.json")
    doc = json.load(open(mpath))
    doc["dst"] = "attacker"
    with open(mpath, "w") as f:
        json.dump(doc, f)
    rep = fsck_fleet(root)
    assert not rep["ok"]
    assert any(
        e.get("artifact") == "fleet_migration_manifest"
        for e in rep["errors"]
    )
    for w in workers.values():
        w.close()
    coord.close()


# ---------------------------------------------------------------------------
# the r19 request-drain race regression (satellite 1): a drain
# requested from another thread mid-tick must WAIT for the in-flight
# scheduling round, and the markers must carry the mid-batch evidence
# ---------------------------------------------------------------------------


def test_request_drain_mid_tick_waits_for_round(tmp_path):
    entered, release = threading.Event(), threading.Event()

    class GateSink(MemorySink):
        def add_batch(self, batch_id, frame):
            entered.set()
            release.wait(10)
            return super().add_batch(batch_id, frame)

    spec = TenantSpec(
        tenant_id="t0", model=_Identity(),
        source=MemorySource(_frames(2)), sink=GateSink(),
    )
    d = ServeDaemon([spec], str(tmp_path / "root"))
    ticker = threading.Thread(target=d.tick)
    ticker.start()
    assert entered.wait(10)  # a batch is in flight inside tick()
    d.request_drain("race")
    drainer = threading.Thread(target=d.drain)
    drainer.start()
    drainer.join(0.3)
    # the fix: drain blocks on the scheduler mutex instead of racing
    # the in-flight round
    assert drainer.is_alive()
    release.set()
    ticker.join(10)
    drainer.join(10)
    assert not drainer.is_alive()
    marker = json.load(open(
        os.path.join(str(tmp_path / "root"), "daemon_drain_marker.json")
    ))
    assert marker["reason"] == "race"
    d.close()


def test_drain_marker_records_mid_batch_tenants(tmp_path):
    class DownSink(MemorySink):
        def add_batch(self, batch_id, frame):
            raise IOError("sink volume down")

    spec = TenantSpec(
        tenant_id="t0", model=_Identity(),
        source=MemorySource(_frames(1)), sink=DownSink(),
    )
    d = ServeDaemon([spec], str(tmp_path / "root"))
    d.tick()  # the batch defers into the WAL — in flight, uncommitted
    d.request_drain("evidence")
    d.drain()
    daemon_marker = json.load(open(
        os.path.join(str(tmp_path / "root"), "daemon_drain_marker.json")
    ))
    assert daemon_marker["mid_batch_tenants"] == ["t0"]
    tenant_marker = json.load(open(os.path.join(
        d.tenant_dir("t0"), "drain_marker.json"
    )))
    assert tenant_marker["was_mid_batch"] is True
    d.close()


# ---------------------------------------------------------------------------
# fleet-flags drift check (the tier-1 wiring of check_fleet_flags)
# ---------------------------------------------------------------------------


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_flags_consistent_cli_coordinator_docs():
    checker = _load_script("check_fleet_flags")
    assert checker.check() == []


# ---------------------------------------------------------------------------
# one process per chip: fleet-serve gives each worker its own chip
# ---------------------------------------------------------------------------


def test_fleet_serve_refuses_more_workers_than_chips(tmp_path, monkeypatch):
    """A chip serves one process: on a TPU host the coordinator counts
    the chips (through a child — it must not open a backend itself) and
    more workers than chips is an error at start, before any spawn."""
    import subprocess

    import sntc_tpu.app as app

    tenants = tmp_path / "tenants.json"
    tenants.write_text(json.dumps({"tenants": [
        {"id": "t0", "model": "m", "watch": "in", "out": "out"},
    ]}))
    monkeypatch.setattr(app, "_local_tpu_chips", lambda: 1)
    monkeypatch.setattr(
        subprocess, "Popen",
        lambda *a, **k: pytest.fail("a worker was spawned"),
    )
    with pytest.raises(SystemExit, match="2 workers but 1 TPU chip"):
        app.main([
            "fleet-serve", "--tenants", str(tenants),
            "--root", str(tmp_path / "root"), "--workers", "2",
        ])


def test_one_chip_env_and_chip_count_off_tpu():
    import sntc_tpu.app as app

    env = app._one_chip_env(2)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # the CPU backend is not exclusive: nothing to count, nothing to pin
    assert app._local_tpu_chips() == 0
