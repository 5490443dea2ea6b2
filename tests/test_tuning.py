"""Persistent tuned-table layer: round-trip, invalidation, precedence.

Covers `repro/kernels/tuning.py` and the three-level block lookup in
`repro/kernels/common.py` (in-process cache beats disk table beats
heuristic), plus the candidates hooks and the sweep harness's smoke path.
"""
import json
import types

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import common, tuning


@pytest.fixture
def table_path(tmp_path, monkeypatch):
    """Point the disk layer at a fresh per-test file; clean caches both
    sides so lookups re-read it."""
    p = tmp_path / "tuned_blocks.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(p))
    common.clear_block_cache()
    common.reset_disk_table()
    yield p
    common.clear_block_cache()
    common.reset_disk_table()


KEY = ("tt.kernel", (64, 64), "int32")


class TestTableIO:
    def test_round_trip(self, table_path):
        tuning.save({KEY: (8, 16)})
        assert table_path.exists()
        assert tuning.load() == {KEY: (8, 16)}

    def test_missing_file_loads_empty(self, table_path):
        assert tuning.load() == {}

    def test_version_mismatch_invalidates(self, table_path):
        tuning.save({KEY: (8, 16)})
        doc = json.loads(table_path.read_text())
        doc["version"]["jax"] = "0.0.0"
        table_path.write_text(json.dumps(doc))
        assert tuning.load() == {}

    def test_platform_mismatch_invalidates(self, table_path):
        tuning.save({KEY: (8, 16)})
        doc = json.loads(table_path.read_text())
        doc["version"]["platform"] = "warp-drive"
        table_path.write_text(json.dumps(doc))
        assert tuning.load() == {}

    def test_device_kind_mismatch_invalidates(self, table_path):
        tuning.save({KEY: (8, 16)})
        doc = json.loads(table_path.read_text())
        assert doc["version"]["device_kind"] == jax.devices()[0].device_kind
        doc["version"]["device_kind"] = "TPU v5 lite"
        table_path.write_text(json.dumps(doc))
        assert tuning.load() == {}

    def test_schema_bump_invalidates(self, table_path):
        tuning.save({KEY: (8, 16)})
        doc = json.loads(table_path.read_text())
        doc["version"]["schema"] = tuning.SCHEMA_VERSION + 1
        table_path.write_text(json.dumps(doc))
        assert tuning.load() == {}

    def test_corrupt_file_recovers(self, table_path):
        table_path.write_text("{this is not json")
        assert tuning.load() == {}
        # and save() replaces the corpse rather than crashing on merge
        tuning.save({KEY: (4, 4)})
        assert tuning.load() == {KEY: (4, 4)}

    def test_malformed_entries_skipped(self, table_path):
        tuning.save({KEY: (8, 16)})
        doc = json.loads(table_path.read_text())
        doc["entries"].append({"kernel": "bad", "shape": "nope",
                               "dtype": 3, "block": []})
        doc["entries"].append("not even a dict")
        table_path.write_text(json.dumps(doc))
        assert tuning.load() == {KEY: (8, 16)}

    def test_save_merges_with_existing(self, table_path):
        other = ("tt.other", (32,), "float32")
        tuning.save({KEY: (8, 16)})
        tuning.save({other: (32,)})
        assert tuning.load() == {KEY: (8, 16), other: (32,)}
        # collisions: the newer write wins
        tuning.save({KEY: (2, 2)})
        assert tuning.load()[KEY] == (2, 2)

    def test_save_without_merge_clobbers(self, table_path):
        tuning.save({KEY: (8, 16)})
        tuning.save({("tt.other", (32,), "f32"): (32,)}, merge=False)
        assert KEY not in tuning.load()

    def test_env_var_overrides_default_path(self, table_path):
        assert tuning.default_path() == str(table_path)

    def test_xdg_default_path(self, monkeypatch, tmp_path):
        monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
        monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
        assert tuning.default_path() == str(
            tmp_path / "xdg" / "repro" / "tuned_blocks.json")


class TestThreeLevelLookup:
    def test_heuristic_when_no_table(self, table_path):
        assert common.pick_block_2d("tt.h", (64, 64)) == (64, 64)

    def test_disk_beats_heuristic(self, table_path):
        tuning.save({("tt.d", (64, 64), "int32"): (4, 4)})
        common.reset_disk_table()
        assert common.pick_block_2d("tt.d", (64, 64)) == (4, 4)

    def test_in_process_beats_disk(self, table_path):
        tuning.save({("tt.p", (64, 64), "int32"): (4, 4)})
        common.reset_disk_table()
        common.set_block("tt.p", (64, 64), jnp.int32, (2, 2))
        assert common.pick_block_2d("tt.p", (64, 64)) == (2, 2)
        # and with the in-process entry gone, disk shows through again
        common.clear_block_cache()
        assert common.pick_block_2d("tt.p", (64, 64)) == (4, 4)

    def test_rows_and_matmul_pickers_hit_disk(self, table_path):
        tuning.save({("tt.rows", (64, 32), "int32"): (8, 32),
                     ("tt.mm", (64, 64, 64), "int32"): (16, 16, 16)})
        common.reset_disk_table()
        assert common.pick_block_rows("tt.rows", (64, 32)) == 8
        assert common.pick_block_matmul("tt.mm", 64, 64, 64) == (16, 16, 16)

    def test_stale_table_falls_back_to_heuristic(self, table_path):
        tuning.save({("tt.s", (64, 64), "int32"): (4, 4)})
        doc = json.loads(table_path.read_text())
        doc["version"]["jax"] = "0.0.0"
        table_path.write_text(json.dumps(doc))
        common.reset_disk_table()
        assert common.pick_block_2d("tt.s", (64, 64)) == (64, 64)

    def test_load_tuned_table_counts(self, table_path):
        tuning.save({("tt.c", (8, 8), "int32"): (8, 8)})
        assert common.load_tuned_table() == 1
        assert common.load_tuned_table(str(table_path)) == 1


class TestCandidatesHooks:
    def test_every_family_enumerates_candidates(self):
        shapes = {
            "cordic_act": (32, 64),
            "cordic_softmax": (16, 64),
            "cordic_mac": (64, 64, 64),
            "flash_attention": (32, 32),
            "wkv": (32, 8),
        }
        for name, shape in shapes.items():
            spec = common.get_kernel(name)
            assert spec.candidates is not None, name
            cands = tuple(spec.candidates(shape, jnp.int32))
            assert cands, name
            for c in cands:
                assert len(c) == len(shape), (name, c)
                assert all(isinstance(b, int) and b >= 1 for b in c), (name, c)

    def test_divisor_families_emit_divisors(self):
        spec = common.get_kernel("cordic_act")
        for br, bc in spec.candidates((24, 36), jnp.int32):
            assert 24 % br == 0 and 36 % bc == 0

    def test_divisor_candidates_helper(self):
        assert common.divisor_candidates(64, 16, 3) == (16, 8, 4)
        assert common.divisor_candidates(7, 512, 4) == (7, 1)
        assert common.divisor_candidates(1, 8) == (1,)


class TestSweepHarness:
    def test_smoke_sweep_persists_and_fresh_lookup_serves(
            self, table_path, tmp_path):
        from benchmarks.tune_bench import sweep
        out = tmp_path / "BENCH_kernels.json"
        report = sweep(smoke=True, repeats=1, families=["cordic_softmax"],
                       out_path=str(out))
        assert len(report["rows"]) == 1
        row = report["rows"][0]
        assert row["us_heuristic"] > 0 and row["us_tuned"] > 0
        assert json.loads(out.read_text())["meta"]["smoke"] is True
        # a fresh lookup state (new process analogue) serves the winner
        common.clear_block_cache()
        common.reset_disk_table()
        shape = tuple(row["shape"])
        assert common.pick_block_rows("cordic_softmax", shape) == \
            row["tuned_block"][0]

    def test_autotune_rejects_keyboard_interrupt(self):
        common.clear_block_cache()

        def run(blk):
            raise KeyboardInterrupt

        with pytest.raises(KeyboardInterrupt):
            common.autotune("tt.ki", (8, 8), jnp.int32, [(8, 8)], run,
                            repeats=1)


class TestServeWarmBoot:
    def test_engine_init_loads_tuned_table(self, table_path):
        from repro.runtime.serve_loop import ServeEngine
        tuning.save({("tt.serve", (8, 8), "int32"): (2, 2)})
        common.reset_disk_table()
        model = types.SimpleNamespace(
            cfg=None,
            prefill=lambda p, b: (_ for _ in ()).throw(AssertionError),
            decode_step=lambda p, st, b: None)
        engine = ServeEngine(model, params=None)
        assert engine.tuned_blocks == 1
        assert common.pick_block_2d("tt.serve", (8, 8)) == (2, 2)
