"""Roofline analyzer tests: HLO parsing on real compiled modules +
synthetic fragments with known answers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo import (analyze, collective_bytes,
                                computation_multipliers, parse_module)
from repro.roofline.model import roofline_terms, wire_bytes
from repro import hw


def test_dot_flops_exact():
    """jit a known matmul; the analyzer must count 2*M*N*K flops."""
    M, K, N = 64, 32, 48

    def f(a, b):
        return a @ b

    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32)).compile().as_text()
    r = analyze(hlo)
    assert r["flops"] == 2 * M * N * K


def test_while_trip_count_multiplies():
    """A scan of 7 matmuls must count 7x the body's flops."""
    M = 32

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((M, M), jnp.float32)).compile().as_text()
    r = analyze(hlo)
    assert r["flops"] == 7 * 2 * M * M * M


def test_collective_bytes_psum():
    import os
    # single-device psum lowers away; use a synthetic fragment instead
    hlo = """\
HloModule test, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

ENTRY %main (p: f32[16,128]) -> f32[16,128] {
  %p = f32[16,128]{1,0} parameter(0)
  ROOT %ar = f32[16,128]{1,0} all-reduce(%p), to_apply=%add
}
"""
    cb = collective_bytes(hlo)
    assert cb["all-reduce"] == 16 * 128 * 4
    assert cb["total"] == 16 * 128 * 4


def test_collectives_inside_while_multiply():
    hlo = """\
HloModule test, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%cond (t: (s32[], f32[8])) -> pred[] {
  %t = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (t2: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t2 = (s32[], f32[8]) parameter(0)
  %i2 = s32[] get-tuple-element(%t2), index=0
  %x = f32[8] get-tuple-element(%t2), index=1
  %one = s32[] constant(1)
  %i3 = s32[] add(%i2, %one)
  %ag = f32[8]{0} all-gather(%x), dimensions={0}
  ROOT %out = (s32[], f32[8]) tuple(%i3, %ag)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8]) tuple(%zero, %p)
  %w = (s32[], f32[8]) while(%t0), condition=%cond, body=%body
  ROOT %r = f32[8]{0} get-tuple-element(%w), index=1
}
"""
    cb = collective_bytes(hlo)
    assert cb["all-gather"] == 5 * 8 * 4


def test_dus_fusion_charged_as_update():
    """In-place cache update inside a scan must cost ~2x the slice, not
    the whole buffer."""
    S, d = 1024, 64

    def f(cache, xs):
        def body(c, inp):
            x, i = inp
            return jax.lax.dynamic_update_slice(c, x[None], (i, 0)), None
        c, _ = jax.lax.scan(body, cache,
                            (xs, jnp.arange(4, dtype=jnp.int32)))
        return c

    hlo = jax.jit(f, donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((S, d), jnp.float32),
        jax.ShapeDtypeStruct((4, d), jnp.float32)).compile().as_text()
    r = analyze(hlo)
    # full-buffer accounting would be >= 4 * S * d * 4 = 1 MiB; the
    # in-place model stays well under one buffer's size
    assert r["hbm_bytes"] < S * d * 4, r["hbm_bytes"]


def test_roofline_terms_math():
    rl = roofline_terms(
        arch="a", shape="s", mesh="m", chips=256,
        hlo_flops=1.97e12,                    # 10 ms of compute
        hlo_bytes=8.19e9,                     # 10 ms of HBM
        coll_payload={"all-reduce": 1e9, "total": 1e9},
        n_params=1e9, n_active=1e9, tokens=1e6, train=True, axis_size=16)
    assert abs(rl.t_compute - 0.01) < 1e-4
    assert abs(rl.t_memory - 0.01) < 1e-4
    want_wire = 1e9 * 2.0 * 15 / 16
    assert abs(rl.t_collective - want_wire / (4 * 50e9)) < 1e-6
    assert rl.bottleneck in ("compute", "memory", "collective")
    assert rl.t_bound == max(rl.t_compute, rl.t_memory, rl.t_collective)


def test_wire_bytes_ring_factors():
    w = wire_bytes({"all-reduce": 100, "all-gather": 100,
                    "all-to-all": 100}, axis_size=4)
    assert abs(w - (200 * 0.75 + 100 * 0.75 + 25 * 0.75)) < 1e-9


def test_hw_table_keyed_by_device_kind():
    v5e = hw.chip("TPU v5 lite")
    assert v5e is hw.TARGET
    assert v5e.peak_flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
    assert hw.VMEM_BYTES == 16 * 1024 * 1024
    assert hw.ICI_LINKS_PER_CHIP * hw.ICI_BW_PER_LINK == 1600e9 / 8
    with pytest.raises(hw.UnknownDevice):
        hw.chip("cpu")
