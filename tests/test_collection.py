"""Tier-1 budget guard: collection-time marker hygiene.

``slow`` means: multi-process tests, chaos and fabric drills, and any
case that costs over 40 s in a run of its own file (inside the whole
gate a case costs about twice that; ROADMAP.md's tier-1 paragraph has
the wall time and the two limits a FILE is held to).  The tier-1 gate
(``pytest -m 'not slow'``) EXECUTES the mesh paths — the EP transports'
forwards and gradients on the eight virtual devices, under ``jax.jit``
as the programs run them, one bare call a transport (an eager
``shard_map`` is a thousand small compiles a call: ISSUE 47) — so only
two classes are held out by rule: end-to-end
chaos drills (a full training job per fault), anywhere, and shard_map
*executions* inside ``test_chaos.py`` (trace-only jaxpr inspection is
its fast-lane form).  Both must carry ``@pytest.mark.slow`` so a new
drill can never silently land in the gate.

The AST rule itself lives in the static-analysis subsystem
(``flashmoe_tpu/staticcheck/lint.py`` — where ``python -m
flashmoe_tpu.staticcheck --lint`` runs it alongside the other rules);
this file is the thin tier-1 wrapper that keeps the historical gate
names and coverage."""

from flashmoe_tpu.staticcheck.lint import (
    DRILL_CALLS, SHARD_MAP_CALLS, check_slow_marks, slow_mark_selfcheck,
)

assert DRILL_CALLS and SHARD_MAP_CALLS  # engine still exports the rule


def test_every_chaos_drill_test_is_slow_marked():
    """Any test in any file that runs a chaos drill must be slow: one
    drill is a whole resilient training job (compile + steps + restore),
    ~5-10s each on CPU."""
    offenders = [str(v) for v in check_slow_marks()
                 if "chaos drill" in v.detail]
    assert not offenders, offenders


def test_chaos_shard_map_executions_are_slow_marked():
    """test_chaos.py may TRACE the ep layers cheaply (jax.make_jaxpr)
    but must not EXECUTE them in the fast lane."""
    offenders = [str(v) for v in check_slow_marks()
                 if "shard_map" in v.detail]
    assert not offenders, offenders


def test_collection_guard_sees_the_known_slow_tests():
    """Self-check: the AST scan actually finds the known drill/execution
    tests — an empty scan would make the guards vacuously green."""
    assert slow_mark_selfcheck() == []
