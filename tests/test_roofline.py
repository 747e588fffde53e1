import numpy as np
import pytest

from repro.configs.base import TRAIN_4K, get_config, get_train_config
from repro.roofline import analysis
from repro.roofline import analytic

HLO = """
HloModule test

%body (p: (s32[], f32[16,8])) -> (s32[], f32[16,8]) {
  %p = (s32[], f32[16,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[16,8] get-tuple-element(%p), index=1
  %ar = f32[16,8] all-reduce(%x), to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[16,8]) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[16,8])) -> pred[] {
  %p = (s32[], f32[16,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(24)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[16,8]) -> f32[16,8] {
  %a = f32[16,8] parameter(0)
  %cp = f32[16,8] collective-permute(%a), source_target_pairs={{0,1}}
  %init = (s32[], f32[16,8]) tuple(s32[] constant(0), %cp)
  %w = (s32[], f32[16,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[16,8] get-tuple-element(%w), index=1
}
"""


def test_parse_collectives_flat():
    st = analysis.parse_collectives(HLO)
    assert st.count_by_kind["all-reduce"] == 1
    assert st.count_by_kind["collective-permute"] == 1
    assert st.bytes_by_kind["collective-permute"] == 16 * 8 * 4


def test_parse_collectives_nested_multiplies_trip_count():
    st = analysis.parse_collectives_nested(HLO)
    # all-reduce sits in a while body with trip count 24
    assert st.bytes_by_kind["all-reduce"] == 24 * 16 * 8 * 4
    assert st.bytes_by_kind["collective-permute"] == 16 * 8 * 4


def test_analytic_train_model_scales_with_tokens():
    cfg = get_config("qwen2-0.5b")
    tcfg = get_train_config("qwen2-0.5b")
    mesh = {"data": 16, "model": 16}
    m1 = analytic.train_model(cfg, TRAIN_4K, tcfg, mesh, 16, 64)
    import dataclasses

    half = dataclasses.replace(TRAIN_4K, global_batch=128)
    m2 = analytic.train_model(cfg, half, tcfg, mesh, 16, 64)
    assert m1.flops_global == pytest.approx(2 * m2.flops_global, rel=1e-6)
    assert m1.collective_bytes_per_chip > 0


def test_model_flops_moe_counts_active_only():
    dense = get_config("mistral-large-123b")
    moe = get_config("mixtral-8x22b")
    f_moe = analysis.model_flops(moe, TRAIN_4K)
    # 39B active of 141B total
    from repro.models import model as M

    ratio = f_moe / (6.0 * M.parameter_count(moe) * TRAIN_4K.global_batch
                     * TRAIN_4K.seq_len)
    assert 0.2 < ratio < 0.35
