"""The ``repro.tune`` subsystem: plan-cache persistence, mode handling,
analytic-tier determinism (in-process and cross-process), and the
feasibility property of every analytic plan (footprint within the staging
budget, MXU alignment, pad-divisibility)."""
import json
import os

import numpy as np
import pytest

from repro import tune
from repro.core.policy import get_policy, registered_policies
from repro.core.roofline import (LANE, SUBLANE, active_chip,
                                 derive_block_caps, matmul_tile_footprint,
                                 staging_budget_bytes)
from repro.tune.cache import PlanCache, cache_dir, plan_cache

from subproc import run_python


# ---------------------------------------------------------------------------
# Cache: LRU + disk persistence
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_persistence(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    c = PlanCache("chipX", "cpu")
    c.put("k1", {"block": [128, 128, 512], "variant": "fused",
                 "source": "measured"}, persist=True)
    # A fresh instance lazily loads the same file.
    c2 = PlanCache("chipX", "cpu")
    assert c2.get("k1")["variant"] == "fused"
    path = c2.path
    assert path.is_file() and str(path).startswith(str(tmp_path))
    payload = json.loads(path.read_text())
    assert payload["version"] == tune.SCHEMA_VERSION


def test_cache_tolerates_corruption_and_version_mismatch(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    c = PlanCache("chipY", "cpu")
    c.path.parent.mkdir(parents=True, exist_ok=True)
    c.path.write_text("{not json!")
    assert c.get("anything") is None          # corrupt file: empty cache
    c.put("k", {"v": 1}, persist=True)        # and it can be rewritten
    assert PlanCache("chipY", "cpu").get("k") == {"v": 1}

    stale = PlanCache("chipZ", "cpu")
    stale.path.parent.mkdir(parents=True, exist_ok=True)
    stale.path.write_text(json.dumps(
        {"version": -1, "plans": {"k": {"v": 2}}}))
    assert PlanCache("chipZ", "cpu").get("k") is None


def test_cache_lru_eviction(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    c = PlanCache("chipL", "cpu", capacity=3)
    for i in range(5):
        c.put(f"k{i}", {"i": i}, persist=False)
    assert c.get("k0") is None and c.get("k1") is None
    assert c.get("k4") == {"i": 4}


def test_cache_registry_and_clear(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    assert plan_cache("c1", "cpu") is plan_cache("c1", "cpu")
    assert plan_cache("c1", "cpu") is not plan_cache("c2", "cpu")
    plan_cache("c1", "cpu").put("k", {"v": 1}, persist=True)
    tune.clear_plan_cache(disk=True)
    assert plan_cache("c1", "cpu").get("k") is None
    assert str(cache_dir()) == str(tmp_path)


# ---------------------------------------------------------------------------
# Modes
# ---------------------------------------------------------------------------

def test_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_TUNE", raising=False)
    assert tune.mode() == "analytic"
    monkeypatch.setenv("REPRO_TUNE", "off")
    assert tune.mode() == "off"
    with tune.tune_mode("analytic"):
        assert tune.mode() == "analytic"
    assert tune.mode() == "off"
    monkeypatch.setenv("REPRO_TUNE", "bogus")
    with pytest.raises(ValueError):
        tune.mode()
    with pytest.raises(ValueError):
        tune.tune_mode("bogus").__enter__()


def test_off_mode_returns_none():
    with tune.tune_mode("off"):
        assert tune.matmul_plan(256, 256, 256, policy="bf16x6") is None
        assert tune.attention_plan(256, 256, 64, 64, policy="bf16x6") is None
        assert tune.paged_plan(256, 2, 64, 64, policy="bf16x6") is None


# ---------------------------------------------------------------------------
# Analytic tier: pure + deterministic
# ---------------------------------------------------------------------------

def test_analytic_plan_is_deterministic_in_process():
    with tune.tune_mode("analytic"):
        plans = {tune.matmul_plan(640, 256, 520, policy="bf16x6")
                 for _ in range(5)}
    assert len(plans) == 1
    (p,) = plans
    assert p.source == "analytic" and p.measured_us is None


def test_analytic_plan_never_touches_disk(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    with tune.tune_mode("analytic"):
        tune.matmul_plan(512, 512, 512, policy="bf16x6")
        tune.attention_plan(512, 512, 128, 128, policy="bf16x6")
    assert list(tmp_path.rglob("*")) == []


@pytest.mark.slow
def test_analytic_plans_identical_across_processes(tmp_path):
    """Cache-determinism smoke: two fresh interpreters emit identical plans
    for the same keys (the CI determinism gate)."""
    code = """
import json
from repro import tune
plans = []
for (m, n, k) in [(512, 512, 512), (64, 2048, 520), (8, 128, 1000)]:
    for pol in ["bf16x3", "bf16x6", "fp32_vpu"]:
        p = tune.matmul_plan(m, n, k, policy=pol)
        plans.append([list(p.block), p.variant, p.predicted_us])
ap = tune.attention_plan(1024, 1024, 128, 128, policy="bf16x6")
plans.append([ap.block_q, ap.block_kv, ap.predicted_us])
pp = tune.paged_plan(256, 2, 64, 64, policy="bf16x6")
plans.append([pp.page_size, pp.pages_per_step])
print(json.dumps(plans))
"""
    outs = [run_python(code, devices=1) for _ in range(2)]
    assert outs[0] == outs[1]
    assert json.loads(outs[0])


# ---------------------------------------------------------------------------
# Feasibility property: every analytic plan fits the budget and aligns.
# ---------------------------------------------------------------------------

def _assert_feasible(m, n, k, policy_name):
    pol = get_policy(policy_name)
    plan = tune.matmul_plan(m, n, k, policy=pol)
    assert plan is not None, (m, n, k, policy_name)
    bm, bn, bk = plan.block
    chip = active_chip()
    # (a) staging feasibility
    fp = matmul_tile_footprint(bm, bn, bk, pol.n_words, plan.variant)
    assert fp <= staging_budget_bytes(chip) <= chip.staging_kib * 1024
    # (b) MXU alignment
    assert bm % SUBLANE == 0 and bn % LANE == 0 and bk % LANE == 0
    # (c) caps
    bm_cap, bn_cap, bk_cap = derive_block_caps(chip, pol.n_words)
    assert bm <= bm_cap and bn <= bn_cap and bk <= bk_cap
    # (d) dividing-or-padded: the padded dim is a multiple of the block
    for dim, blk, align in ((m, bm, SUBLANE), (n, bn, LANE), (k, bk, LANE)):
        padded = -(-dim // blk) * blk
        assert padded % blk == 0
        assert padded - dim < blk + align   # no more than one block of pad
    # (e) the variant is one the policy can execute
    assert plan.variant in tune.matmul_variants(pol)


def test_plan_feasibility_seeded_sweep():
    """Deterministic stand-in for the hypothesis property below (always
    runs, even without hypothesis installed)."""
    rng = np.random.default_rng(0)
    with tune.tune_mode("analytic"):
        for _ in range(25):
            m = int(rng.integers(1, 2049))
            n = int(rng.integers(1, 2049))
            k = int(rng.integers(1, 2049))
            for pol in registered_policies():
                _assert_feasible(m, n, k, pol)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(1, 4096), st.integers(1, 4096), st.integers(1, 4096),
           st.sampled_from(registered_policies()))
    def test_plan_feasibility_property(m, n, k, policy_name):
        with tune.tune_mode("analytic"):
            _assert_feasible(m, n, k, policy_name)


# ---------------------------------------------------------------------------
# Tiling edge cases through the kernel-default chooser
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [
    (1, 1, 1),           # everything below one tile
    (5, 70, 33),         # m < SUBLANE, n < LANE
    (8, 128, 2048),      # bk cap engaged (k > 512 on v5e)
    (1000, 520, 520),    # nothing divides
])
def test_default_blocks_edge_cases(m, n, k):
    from repro.kernels.tcec_matmul import default_blocks, pad_amounts
    bm, bn, bk = default_blocks(m, n, k)
    chip = active_chip()
    caps = derive_block_caps(chip)
    assert bm % SUBLANE == 0 and bn % LANE == 0 and bk % LANE == 0
    assert (bm, bn, bk) <= caps
    mp, np_, kp = pad_amounts(m, n, k, (bm, bn, bk))
    assert mp % bm == 0 and np_ % bn == 0 and kp % bk == 0
    assert mp >= m and np_ >= n and kp >= k


def test_default_blocks_v5e_matches_legacy():
    """The chip-derived caps reproduce the previously hardcoded defaults
    (the v5e derivation is the source of the old constants)."""
    from repro.kernels.tcec_matmul import default_blocks
    from repro.core.roofline import TPU_V5E
    assert derive_block_caps(TPU_V5E) == (128, 128, 512)
    assert default_blocks(4096, 4096, 4096, TPU_V5E) == (128, 128, 512)
    assert default_blocks(5, 70, 33, TPU_V5E) == (8, 128, 128)


# ---------------------------------------------------------------------------
# Measure tier (in-process, tiny shapes) + persistence
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_measure_mode_persists_winner(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("REPRO_TUNE_TOPK", "2")
    tune.clear_plan_cache()
    with tune.tune_mode("measure"):
        p1 = tune.matmul_plan(16, 128, 128, policy="bf16x3", site="t")
    assert p1.source == "measured" and p1.measured_us is not None
    files = list(tmp_path.rglob("*.json"))
    assert files, "measured winner was not persisted"
    # Second query (fresh in-memory cache) is served from disk, no re-timing.
    tune.clear_plan_cache()
    with tune.tune_mode("measure"):
        p2 = tune.matmul_plan(16, 128, 128, policy="bf16x3", site="t")
    assert p2 == p1


# ---------------------------------------------------------------------------
# Candidate spaces
# ---------------------------------------------------------------------------

def test_matmul_variants_per_policy():
    assert tune.matmul_variants(get_policy("fp32_vpu")) == ("vpu",)
    assert tune.matmul_variants(get_policy("bf16x1")) == ("fused",)
    assert tune.matmul_variants(get_policy("bf16x6")) == \
        ("fused", "staged", "staged_db")


def test_candidates_nonempty_and_feasible():
    for pol in registered_policies():
        cands = tune.matmul_candidates(7, 7, 7, get_policy(pol))
        assert cands
    budget = staging_budget_bytes(active_chip())
    for c in tune.matmul_candidates(2048, 2048, 2048, get_policy("bf16x6")):
        assert matmul_tile_footprint(*c.block, 3, c.variant) <= budget


def test_paged_candidates_respect_seq_bound():
    cands = tune.paged_candidates(16)
    assert cands and all(c.page_size <= 16 for c in cands)
    assert tune.paged_candidates(1)   # never empty


# ---------------------------------------------------------------------------
# Chip identity: the analytic target is read from the device on a TPU.
# ---------------------------------------------------------------------------

class _StubDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def _stub_devices(monkeypatch, platform, kind):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [_StubDevice(platform, kind)])


def test_active_chip_reads_tpu_device_kind(monkeypatch):
    from repro.core.roofline import TPU_V5E
    monkeypatch.delenv("REPRO_CHIP", raising=False)
    _stub_devices(monkeypatch, "tpu", "TPU v5 lite")
    assert active_chip() is TPU_V5E
    monkeypatch.setenv("REPRO_CHIP", "tpu-v5e")
    assert active_chip() is TPU_V5E


def test_active_chip_raises_on_unknown_tpu_kind(monkeypatch):
    monkeypatch.delenv("REPRO_CHIP", raising=False)
    _stub_devices(monkeypatch, "tpu", "TPU v9 hypothetical")
    with pytest.raises(ValueError, match="TPU v9 hypothetical"):
        active_chip()


def test_active_chip_raises_when_repro_chip_contradicts_device(monkeypatch):
    monkeypatch.setenv("REPRO_CHIP", "a100-sxm4")
    _stub_devices(monkeypatch, "tpu", "TPU v5 lite")
    with pytest.raises(ValueError, match="contradicts"):
        active_chip()


def test_active_chip_off_tpu_keeps_v5e_default_and_repro_chip(monkeypatch):
    from repro.core.roofline import A100_SXM4, TPU_V5E
    monkeypatch.delenv("REPRO_CHIP", raising=False)
    _stub_devices(monkeypatch, "cpu", "cpu")
    assert active_chip() is TPU_V5E
    monkeypatch.setenv("REPRO_CHIP", "a100-sxm4")
    assert active_chip() is A100_SXM4
