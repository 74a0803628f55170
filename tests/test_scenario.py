"""Scenario engine: schema validation, deterministic churn rotation,
and the tier-1 end-to-end scenarios.

`test_churn_small_end_to_end` is the PR's validator-churn acceptance
test: ≥25% of the active window rotates every K heights through ≥3
full epochs, and BOTH rotation seams are asserted — PR 14's
speculated-round rebuild (`pipeline_stats["valset_rebuilds"]`) and
PR 15's bisection bridging from the genesis valset across every
epoch boundary — with the Nemesis no-fork/commit-agreement invariants
green throughout. The heavy library entries (flash crowd, regional
outage, churn storm, partition-during-churn) run slow-marked.
"""

from __future__ import annotations

import pytest

from tendermint_tpu.testing.scenario import (
    SCENARIO_LIBRARY,
    ChurnApp,
    ScenarioRunner,
    churn_app_factory,
    run_library,
    validate_scenario,
)


class TestSchema:
    def test_defaults_fill_in(self):
        spec = validate_scenario({"name": "x"})
        assert spec["nodes"] == 4
        assert spec["kind"] == "core"
        assert spec["run"]["target_height"] == 20

    def test_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown scenario keys"):
            validate_scenario({"name": "x", "topologee": {}})

    def test_unknown_action_rejected(self):
        with pytest.raises(ValueError, match="unknown timeline action"):
            validate_scenario(
                {"name": "x", "timeline": [{"at_height": 1, "action": "explode"}]}
            )

    def test_timeline_event_needs_a_trigger(self):
        with pytest.raises(ValueError, match="at_height or at_s"):
            validate_scenario({"name": "x", "timeline": [{"action": "heal"}]})

    def test_churn_requires_active_window(self):
        with pytest.raises(ValueError, match="n_active"):
            validate_scenario({"name": "x", "churn": {"every": 4, "shift": 1}})

    def test_load_requires_full_nodes(self):
        with pytest.raises(ValueError, match="kind=full"):
            validate_scenario({"name": "x", "load": {"rate": 10}})

    def test_library_specs_all_validate(self):
        for name, spec in SCENARIO_LIBRARY.items():
            validated = validate_scenario(spec)
            assert validated["name"] == name


class TestChurnApp:
    def _pool(self, n=6):
        return [bytes([i]) * 32 for i in range(n)]

    def test_no_rotation_off_boundary(self):
        app = ChurnApp(self._pool(), active=4, every=4, shift=1)
        for h in (1, 2, 3, 5, 7, 9):
            assert app.end_block(h) == []

    def test_rotation_diff_is_25_percent(self):
        pool = self._pool()
        app = ChurnApp(pool, active=4, every=4, shift=1)
        changes = app.end_block(4)  # epoch 0 {0,1,2,3} -> epoch 1 {1,2,3,4}
        assert [(c.pub_key, c.power) for c in changes] == [
            (pool[0], 0),  # removed
            (pool[4], 10),  # admitted
        ]

    def test_window_wraps_the_pool(self):
        pool = self._pool()
        app = ChurnApp(pool, active=4, every=4, shift=1)
        changes = app.end_block(12)  # epoch 3 {3,4,5,0}: wraps to index 0
        assert (pool[0], 10) in [(c.pub_key, c.power) for c in changes]

    def test_two_apps_agree(self):
        """Rotation is a pure function of height — the determinism
        consensus needs from every replica's EndBlock."""
        a = ChurnApp(self._pool(), active=4, every=3, shift=2)
        b = ChurnApp(self._pool(), active=4, every=3, shift=2)
        for h in range(1, 20):
            assert [(c.pub_key, c.power) for c in a.end_block(h)] == [
                (c.pub_key, c.power) for c in b.end_block(h)
            ]

    def test_factory_pool_matches_genesis(self):
        from tendermint_tpu.testing.nemesis import make_genesis

        factory = churn_app_factory(6, "c", active=4, every=4, shift=1)
        app = factory()
        _, privs = make_genesis(6, chain_id="c", n_active=4)
        changes = app.end_block(4)
        admitted = {c.pub_key for c in changes if c.power > 0}
        assert admitted == {privs[4].pub_key.data}


class TestEndToEnd:
    def test_churn_small_end_to_end(self, tmp_path):
        """≥25% window rotation every 4 heights, ≥3 full epochs:
        speculation rebuilds fire at every boundary, the light client
        bisects genesis→tip across all rotations, no fork."""
        report = ScenarioRunner(home=str(tmp_path)).run(
            SCENARIO_LIBRARY["churn_small"]
        )
        assert report["ok"], report["failures"]
        assert report["epochs"] >= 3
        assert report["valset_rebuilds"] >= 3  # PR 14 seam exercised
        assert report["bisection"]["verified_to"] >= 16  # PR 15 seam exercised
        assert min(report["heights"]) >= 16

    def test_slow_wan_validator_end_to_end(self, tmp_path):
        """Adaptive timeouts learn the slow path: derived propose
        timeout converges above the injected one-way delay and round
        skips stop once warmed."""
        report = ScenarioRunner(home=str(tmp_path)).run(
            SCENARIO_LIBRARY["slow_wan_validator"]
        )
        assert report["ok"], report["failures"]
        assert (
            report["propose_timeout_s"]["min"] > report["max_one_way_delay_s"]
        )
        assert report["round_skips_post_warm"] == 0


@pytest.mark.slow
class TestLibraryHeavy:
    @pytest.mark.parametrize(
        "name",
        ["regional_outage", "churn_storm", "partition_during_churn", "flash_crowd"],
    )
    def test_library_scenario(self, name, tmp_path):
        report = ScenarioRunner(home=str(tmp_path)).run(SCENARIO_LIBRARY[name])
        assert report["ok"], (name, report["failures"])

    def test_run_library_filters(self, tmp_path):
        reports = run_library(names=["churn_small"], home=str(tmp_path))
        assert [r["scenario"] for r in reports] == ["churn_small"]
