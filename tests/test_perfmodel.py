"""Guards for the roofline instruments: the scan-aware FLOP counter and
the trip-count-scaled HLO cost walker (EXPERIMENTS.md §Roofline)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.perfmodel.flops import count_fn_flops


def test_flops_matmul_exact():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    fl = count_fn_flops(lambda x, y: x @ y, a, b)
    assert fl == 2 * 64 * 128 * 32


def test_flops_scan_multiplies_by_trip_count():
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    fl = count_fn_flops(f, x, w)
    base = 2 * 32 * 32 * 32
    assert abs(fl - 7 * base) < base * 0.01


def test_flops_remat_counted_once():
    """Remat bodies count once (the recompute belongs to the schedule,
    not the model's intrinsic work)."""
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    plain = count_fn_flops(lambda x, w: x @ w, x, w)
    rematted = count_fn_flops(jax.checkpoint(lambda x, w: x @ w), x, w)
    assert rematted == plain


def test_flops_grad_includes_backward():
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 16), jnp.float32)

    fwd = count_fn_flops(lambda w, x: jnp.sum(x @ w), w, x)
    both = count_fn_flops(
        lambda w, x: jax.grad(lambda ww: jnp.sum(x @ ww))(w), w, x)
    # grad wrt w adds one more matmul (x.T @ g): ~2x the forward
    assert 1.8 * fwd < both < 3.0 * fwd


def test_hlo_walker_scales_loop_bodies():
    """analyze_hlo must charge a scanned matmul ~N times, where XLA's own
    HLO text contains the body once."""
    from repro.launch.dryrun import analyze_hlo

    w = jnp.ones((128, 128), jnp.float32)

    def make(n):
        def f(x):
            def body(c, _):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return y
        return jax.jit(f).lower(
            jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()

    b4 = analyze_hlo(make(4).as_text())["hbm_bytes"]
    b12 = analyze_hlo(make(12).as_text())["hbm_bytes"]
    ratio = b12 / max(b4, 1)
    assert 2.0 < ratio < 4.0, (b4, b12)  # ~3x for 3x the trip count


def test_hlo_walker_finds_known_trip_count():
    from repro.launch.dryrun import _TRIP_RE

    line = ('%while.1 = (s32[]) while(%t), condition=%c, body=%b, '
            'backend_config={"known_trip_count":{"n":"48"}}')
    m = _TRIP_RE.search(line)
    assert m and int(m.group(1)) == 48


def test_bottleneck_tie_break_is_deterministic():
    """Equal roofline terms must resolve by the documented priority
    (compute > memory > collective), not by string comparison of the
    labels — the tuple-max fallthrough this replaces picked 'memory' on
    an all-zero tie purely because 'm' > 'c'."""
    from repro.perfmodel.roofline import pick_bottleneck

    assert pick_bottleneck(0.0, 0.0, 0.0) == "compute"
    assert pick_bottleneck(1.0, 1.0, 1.0) == "compute"
    assert pick_bottleneck(1.0, 2.0, 2.0) == "memory"
    assert pick_bottleneck(0.0, 0.0, 1e-9) == "collective"


def test_bottleneck_dominant_term_wins():
    from repro.perfmodel.roofline import pick_bottleneck

    assert pick_bottleneck(3.0, 1.0, 2.0) == "compute"
    assert pick_bottleneck(1.0, 3.0, 2.0) == "memory"
    assert pick_bottleneck(1.0, 2.0, 3.0) == "collective"


def test_walker_collectives_empty_on_single_device():
    from repro.launch.dryrun import analyze_hlo

    f = jax.jit(lambda x: x * 2 + 1)
    hlo = f.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)) \
        .compile().as_text()
    out = analyze_hlo(hlo)
    assert out["collectives"].get("total", 0) == 0
    assert out["hbm_bytes"] > 0


def test_device_peaks_keyed_by_device_kind():
    """One peak table keyed by jax's device_kind: v5e carries its
    published bf16/int8/HBM peaks, and an unknown kind is an error, not a
    silent v5e stand-in (attribution on an unlisted device raises)."""
    from repro.obs.attribution import AttributionExecutor
    from repro.perfmodel import roofline

    v5e = roofline.device_peaks("TPU v5 lite")
    assert v5e is roofline.V5E
    assert (v5e.bf16_flops, v5e.int8_ops, v5e.hbm_bw) == (197e12, 393e12,
                                                          819e9)
    assert "TPU v5e" in v5e.source
    assert roofline.PEAK_FLOPS == v5e.bf16_flops
    assert roofline.HBM_BW == v5e.hbm_bw
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.device_peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        AttributionExecutor(inner=None)      # the CPU host is not listed
