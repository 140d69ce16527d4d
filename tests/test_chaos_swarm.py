"""Agent-swarm stress + linearizability checker (DESIGN.md §15).

The acceptance gate for the chaos tier: 240 seeded adversarial
schedules (contention, crashes at every publication seam, failed store
writes, abandoned branches, quarantine reuse, concurrent GC) with ZERO
linearizability violations, every crash point leaving a readable and
GC-recoverable catalog, and the checker itself proven non-vacuous
against hand-built bad histories.
"""
import dataclasses

import pytest

from repro.chaos import (FaultPlan, FaultRule, InjectedCrash, SwarmConfig,
                         check_history, check_swarm, fault_injection,
                         run_swarm)
from repro.chaos.swarm import AgentRecord
from repro.core.catalog import Catalog, Visibility
from repro.core.transactions import RunRegistry, TransactionalRun

BASE_RULES = (FaultRule("txn.commit.post_merge", "crash", 0.10),
              FaultRule("txn.begin.post_branch", "crash", 0.03),
              FaultRule("txn.commit.pre_merge", "delay", 0.20,
                        delay_s=0.001),
              FaultRule("store.put", "fail", 0.08))

# four regimes x 60 seeds = 240 adversarial schedules
REGIMES = {
    "calm": SwarmConfig(n_agents=6, runs_per_agent=2, gc_every=3),
    # the pre_merge delay holds publishers between verification and
    # CAS, so concurrent merges actually land in the window
    "contended": SwarmConfig(n_agents=8, runs_per_agent=2, hot_tables=1,
                             p_contended=0.8, p_multi=0.0, p_violate=0.0,
                             p_abandon=0.0, p_reuse=0.0, gc_every=4,
                             fault_rules=(FaultRule(
                                 "txn.commit.pre_merge", "delay", 0.8,
                                 delay_s=0.003),)),
    "faulted": SwarmConfig(n_agents=6, runs_per_agent=2, gc_every=3,
                           use_store=True, fault_rules=BASE_RULES,
                           fault_budget=8),
    "hostile": SwarmConfig(
        n_agents=6, runs_per_agent=2, gc_every=2, use_store=True,
        p_violate=0.2, p_abandon=0.15, p_reuse=0.2,
        fault_rules=BASE_RULES + (
            FaultRule("txn.commit.pre_rebase", "crash", 0.05),
            FaultRule("txn.commit.post_rebase", "crash", 0.05)),
        fault_budget=12),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("batch", range(3))
def test_seeded_swarms_are_linearizable(regime, batch):
    base = REGIMES[regime]
    for i in range(20):
        seed = f"{regime}-{batch * 20 + i}"
        res = run_swarm(dataclasses.replace(base, seed=seed))
        violations = check_swarm(res)
        assert not violations, (
            f"seed {seed!r} (replayable): {violations}\n"
            f"injected={res.plan.injected}")
        assert len(res.records) == base.n_agents * base.runs_per_agent


def test_single_agent_swarm_replays_exactly():
    """With one agent the schedule is sequential, so a seed replays the
    ENTIRE history — outcomes, fault log, final heads — bit for bit."""
    cfg = SwarmConfig(n_agents=1, runs_per_agent=8, seed="replay",
                      use_store=True, fault_rules=BASE_RULES, gc_every=3)
    a, b = run_swarm(cfg), run_swarm(cfg)
    assert [(r.run_id, r.intent, r.outcome, r.tables)
            for r in a.records] == \
           [(r.run_id, r.intent, r.outcome, r.tables)
            for r in b.records]
    assert a.plan.injected == b.plan.injected
    assert a.catalog.tables("main") == b.catalog.tables("main")


def test_swarm_registry_agrees_with_records():
    res = run_swarm(SwarmConfig(n_agents=6, runs_per_agent=2, seed=5))
    by_id = {s.run_id: s for s in res.registry.runs()}
    for r in res.records:
        if r.outcome == "committed":
            assert by_id[r.run_id].status == "committed"
            assert by_id[r.run_id].final_commit == r.final_commit
        elif r.outcome == "aborted":
            assert by_id[r.run_id].status == "aborted"
        elif r.outcome == "abandoned":
            # walked away without abort: registry still says running —
            # exactly the record GC's liveness input must override
            assert by_id[r.run_id].status == "running"


def test_swarm_final_gc_leaves_no_txn_debris():
    cfg = SwarmConfig(n_agents=8, runs_per_agent=3, seed=11,
                      p_abandon=0.3, use_store=True,
                      fault_rules=BASE_RULES, fault_budget=10)
    res = run_swarm(cfg)
    assert not check_swarm(res)
    for b in res.catalog.branches():
        vis = res.catalog.branch_info(b).visibility
        assert vis not in (Visibility.TXN, Visibility.ABORTED), (
            f"{b} survived the final sweep as {vis}")


def test_swarm_contention_exercises_rebase_and_backoff():
    res = run_swarm(dataclasses.replace(REGIMES["contended"],
                                        seed="backoff"))
    assert not check_swarm(res)
    # a conflicted publisher retried (and may then have committed or
    # aborted on the hot-table rebase conflict — both are legal)
    attempts = [s.publish_attempts for s in res.registry.runs()]
    assert attempts and max(attempts) > 1, (
        "contended regime never conflicted — not stressing publication")
    assert res.clock.sleep_count > 0      # backoff went through FakeClock


# ---------------------------------------------------------------------------
# every crash point leaves a readable, recoverable catalog
# ---------------------------------------------------------------------------

CRASH_POINTS = ["txn.begin.post_branch", "txn.commit.pre_merge",
                "txn.commit.post_merge", "store.put"]


@pytest.mark.parametrize("point", CRASH_POINTS)
def test_crash_point_recovery(point):
    cfg = SwarmConfig(
        n_agents=3, runs_per_agent=2, seed=f"crash-{point}",
        use_store=True,
        fault_rules=(FaultRule(point, "crash", 1.0),), fault_budget=3)
    res = run_swarm(cfg)
    assert not check_swarm(res)           # includes catalog-readable
    crashed = [r for r in res.records if r.outcome == "crashed"]
    assert crashed, f"rate-1.0 crash rule at {point} never fired"
    # recovery: after the final sweep a fresh run publishes normally
    with TransactionalRun(res.catalog, "main", run_id="after") as txn:
        txn.write_tables({"after": "s@after"})
        txn.verify(lambda read: read("after"))
    assert res.catalog.tables("main")["after"] == "s@after"


def test_mid_rebase_crash_under_contention():
    """Crash at the rebase seams specifically, with enough contention
    that rebases actually happen."""
    cfg = SwarmConfig(
        n_agents=8, runs_per_agent=2, seed="rebase-crash", hot_tables=1,
        p_contended=0.9, p_multi=0.0, p_violate=0.0, p_abandon=0.0,
        p_reuse=0.0,
        fault_rules=(FaultRule("txn.commit.pre_rebase", "crash", 0.3),
                     FaultRule("txn.commit.post_rebase", "crash", 0.3)),
        fault_budget=5)
    res = run_swarm(cfg)
    assert not check_swarm(res)


# ---------------------------------------------------------------------------
# the checker is not vacuous: hand-built bad histories must be flagged
# ---------------------------------------------------------------------------

def _rec(**kw):
    base = dict(agent=0, idx=0, run_id="r0", intent="disjoint")
    base.update(kw)
    return AgentRecord(**base)


def _one_good_run(cat, rid, tables):
    reg = RunRegistry()
    with TransactionalRun(cat, "main", run_id=rid, registry=reg) as txn:
        txn.write_tables(tables)
        txn.verify(lambda read: None)
    return txn.final_commit.id


def test_checker_flags_partial_publication():
    cat = Catalog()
    cid = _one_good_run(cat, "r0", {"a": "a@r0"})
    rec = _rec(run_id="r0", outcome="committed", final_commit=cid,
               verified_head=cid, tables={"a": "a@r0", "b": "b@r0"})
    [v] = check_history(cat, [rec])
    assert "partial publication" in v


def test_checker_flags_early_visibility():
    cat = Catalog()
    cat.write_table("main", "a", "a@r0")          # leaked BEFORE publish
    cid = _one_good_run(cat, "r0", {"a": "a@r0", "b": "b@r0"})
    rec = _rec(run_id="r0", outcome="committed", final_commit=cid,
               verified_head=cid, tables={"a": "a@r0", "b": "b@r0"})
    violations = check_history(cat, [rec])
    assert any("BEFORE publication" in v for v in violations)


def test_checker_flags_aborted_leak():
    cat = Catalog()
    cat.write_table("main", "a", "a@dead", run_id=None)
    rec = _rec(run_id="dead", outcome="aborted", tables={"a": "a@dead"})
    [v] = check_history(cat, [rec])
    assert "leaked" in v


def test_checker_flags_aborted_run_with_chain_commit():
    cat = Catalog()
    _one_good_run(cat, "dead", {"a": "a@dead"})
    rec = _rec(run_id="dead", outcome="aborted", tables={"a": "a@dead"})
    violations = check_history(cat, [rec])
    assert any("are on 'main'" in v for v in violations)


def test_checker_flags_unverified_publication():
    cat = Catalog()
    cid = _one_good_run(cat, "r0", {"a": "a@r0"})
    rec = _rec(run_id="r0", outcome="committed", final_commit=cid,
               verified_head="somethingelse", tables={"a": "a@r0"})
    violations = check_history(cat, [rec])
    assert any("unverified state" in v for v in violations)


def test_checker_flags_mystery_publication():
    cat = Catalog()
    _one_good_run(cat, "ghost", {"a": "a@ghost"})
    violations = check_history(cat, [])           # nobody owns that run
    assert any("mystery publication" in v for v in violations)


def test_checker_flags_illegal_quarantine_merge_and_branch_loss():
    cat = Catalog()
    violations = check_history(cat, [
        _rec(run_id="q0", outcome="released", illegal_merge=True),
        _rec(run_id="l0", outcome="branch_lost", error="gone")])
    assert any("Fig. 4" in v for v in violations)
    assert any("GC collected live state" in v for v in violations)


def test_checker_accepts_lost_ack_crash_as_published():
    """A crash after merge (lost ack) is held to committed-run rules —
    and passes them when the publication was in fact atomic."""
    cat = Catalog()
    reg = RunRegistry()
    txn = TransactionalRun(cat, "main", run_id="r0", registry=reg)
    txn.begin()
    txn.write_tables({"a": "a@r0", "b": "b@r0"})
    plan = FaultPlan(0, (FaultRule("txn.commit.post_merge",
                                   "crash", 1.0),))
    with fault_injection(plan):
        with pytest.raises(InjectedCrash):
            txn.commit()
    rec = _rec(run_id="r0", outcome="crashed",
               tables={"a": "a@r0", "b": "b@r0"}, branch=txn.branch)
    assert check_history(cat, [rec]) == []
    # ... and is still checked: claim a table the commit doesn't carry
    rec2 = _rec(run_id="r0", outcome="crashed",
                tables={"a": "a@r0", "c": "c@r0"})
    assert any("partial publication" in v
               for v in check_history(cat, [rec2]))


def test_post_merge_failure_never_aborts_a_published_run():
    """An ordinary error after the merge CAS lands in the lost-ack
    window: the run's state is public, so it must not be marked
    aborted (its branch would become reusable). The swarm records it as
    ``failed``, and the checker holds it to committed-run rules."""
    from repro.chaos import InjectedFault

    cat = Catalog()
    reg = RunRegistry()
    txn = TransactionalRun(cat, "main", run_id="r0", registry=reg)
    txn.begin()
    txn.write_tables({"a": "a@r0"})
    plan = FaultPlan(0, (FaultRule("txn.commit.post_merge",
                                   "fail", 1.0),))
    with fault_injection(plan):
        with pytest.raises(InjectedFault):
            txn.commit()
    assert cat.tables("main")["a"] == "a@r0"
    assert reg.get_run("r0").status == "running"
    assert cat.branch_info(txn.branch).visibility is Visibility.TXN
    rec = _rec(run_id="r0", outcome="failed", tables={"a": "a@r0"},
               branch=txn.branch)
    assert check_history(cat, [rec]) == []
    res = run_swarm(SwarmConfig(
        n_agents=2, runs_per_agent=1, seed=0, hot_tables=1,
        p_contended=0.0, p_multi=0.0, p_violate=0.0, p_abandon=0.0,
        p_reuse=0.0, gc_every=0, fault_rules=(FaultRule(
            "txn.commit.post_merge", "fail", 0.25),)))
    assert check_swarm(res) == []
