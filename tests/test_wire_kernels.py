"""Fused wire-kernel validation (``kernels/wire.py`` via ``kernels/ops``).

Three kernels fuse the federated round's wire hot path — per-silo
clip + DP noise + int8 quantize over the (J, P) matrix, the masked /
weighted (trimmed-)mean reduction, and the Newton–Schulz sqrt step —
and each is pinned to a pure-jnp oracle in ``kernels/ref.py`` plus the
live runtime component it replaces (PrivacyPolicy, the aggregators,
core.barycenter's sqrtm).

Comparisons are JIT vs JIT: the runtime only ever executes these stages
inside the compiled round, and eager-mode XLA contracts FMAs
differently (a 1-ulp artifact, not a semantic difference), so the
honest bit-exactness contract is between compiled programs. Kernels run
in interpret mode on CPU; hypothesis is optional — without it the
property sweeps degrade to fixed seeded parameter grids over the same
domain (same shapes drawn, fewer of them).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.barycenter import sqrtm_newton_schulz
from repro.federated.aggregation import MeanAggregator, TrimmedMeanAggregator
from repro.federated.privacy import PrivacyPolicy
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)

# J deliberately includes primes (no block divides them except 1) and
# P values that are not multiples of any kernel block size, so the
# block-partitioning logic is exercised, not just the aligned fast path.
SHAPES = [(1, 1), (2, 3), (3, 64), (4, 8), (7, 129), (13, 257), (16, 512)]


def _mat(shape, dtype=jnp.float32, salt=0):
    return jax.random.normal(
        jax.random.fold_in(KEY, salt), shape, jnp.float32).astype(dtype)


def _mask(J, pattern, salt=0):
    if pattern == "all":
        return jnp.ones((J,), jnp.float32)
    if pattern == "none":
        return jnp.zeros((J,), jnp.float32)
    bits = jax.random.bernoulli(jax.random.fold_in(KEY, 100 + salt), 0.6, (J,))
    return bits.astype(jnp.float32)


def _keys(J, salt=0):
    base = jax.random.fold_in(KEY, 200 + salt)
    return jax.vmap(lambda j: jax.random.fold_in(base, j))(jnp.arange(J))


def _exact(a, b):
    if isinstance(a, tuple):
        for x, y in zip(a, b, strict=True):
            _exact(x, y)
        return
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# fused upload: clip + noise + mask + quantize
# ---------------------------------------------------------------------------

UPLOAD_CONFIGS = [
    # (clip_norm, noise_multiplier, quantize, use_reference)
    (None, 0.0, False, False),      # pure mask select (passthrough)
    (None, 0.0, True, False),       # quantize only
    (0.5, 0.0, False, False),       # clip only
    (0.5, 1.1, False, False),       # clip + DP noise
    (0.5, 1.1, True, False),        # the full DP + int8 wire
    (0.7, 0.0, False, True),        # delta-vs-reference clip
    (0.7, 0.9, True, True),         # reference + noise + quantize
]


def _run_upload(x, mask, keys, refrow, clip, nm, quant):
    got = ops.wire_upload(
        x, mask, keys=keys if nm > 0 else None, reference=refrow,
        clip_norm=clip, noise_multiplier=nm, quantize=quant)
    oracle = jax.jit(functools.partial(
        ref.wire_upload_ref, clip_norm=clip, noise_multiplier=nm,
        quantize=quant))
    want = oracle(x, mask=mask, keys=keys if nm > 0 else None,
                  reference=refrow)
    _exact(got, want)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("config", UPLOAD_CONFIGS)
@pytest.mark.parametrize("pattern", ["all", "none", "random"])
def test_upload_matches_oracle(shape, config, pattern):
    J, P = shape
    clip, nm, quant, use_ref = config
    x = _mat((J, P), salt=J * 1000 + P)
    mask = _mask(J, pattern, salt=J)
    keys = _keys(J, salt=P)
    refrow = 0.3 * _mat((P,), salt=P + 5) if use_ref else None
    _run_upload(x, mask, keys, refrow, clip, nm, quant)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_upload_input_dtypes(dtype):
    """Inputs upcast to f32 at the kernel edge, like the oracle."""
    x = _mat((5, 33), dtype=dtype)
    mask = _mask(5, "random")
    got = ops.wire_upload(x, mask, clip_norm=0.5, quantize=True)
    oracle = jax.jit(functools.partial(
        ref.wire_upload_ref, clip_norm=0.5, quantize=True))
    _exact(got, oracle(x, mask=mask))


def test_upload_block_rows_invariance():
    """Different row tilings of the same input agree bitwise (each row's
    pipeline is independent of which block it lands in)."""
    x = _mat((12, 96))
    mask = _mask(12, "random")
    keys = _keys(12)
    outs = [ops.wire_upload(x, mask, keys=keys, clip_norm=0.4,
                            noise_multiplier=1.0, quantize=True,
                            block_rows=br) for br in (1, 3, 12)]
    _exact(outs[0], outs[1])
    _exact(outs[0], outs[2])


def test_upload_noise_requires_clip_and_keys():
    x = _mat((3, 4))
    mask = _mask(3, "all")
    with pytest.raises(ValueError):
        ops.wire_upload(x, mask, noise_multiplier=1.0, clip_norm=None)
    with pytest.raises(ValueError):
        ops.wire_upload(x, mask, noise_multiplier=1.0, clip_norm=1.0,
                        keys=None)


class TestPrivacyStreamBitExact:
    """The kernel's in-row noise is the SAME stream PrivacyPolicy draws:
    fold the policy's upload key per silo, and the fused row equals the
    policy's privatize of that row — bit for bit, same round key."""

    def _policy_rows(self, pol, x, round_key, t):
        J = x.shape[0]
        priv = jax.jit(lambda v, k: pol.privatize(v, k))
        rows = [priv(x[j], pol.upload_key(round_key, t, j))
                for j in range(J)]
        return jnp.stack(rows)

    @pytest.mark.parametrize("t", [0, 3])
    @pytest.mark.parametrize("shape", [(1, 5), (4, 37), (7, 129)])
    def test_stream_matches_policy(self, shape, t):
        J, P = shape
        pol = PrivacyPolicy(clip_norm=0.7, noise_multiplier=1.3)
        round_key = jax.random.PRNGKey(123)
        x = _mat((J, P), salt=77)
        keys = jax.vmap(
            lambda s: jax.random.fold_in(pol.upload_key(round_key, t, s), 0)
        )(jnp.arange(J))
        got = ops.wire_upload(
            x, jnp.ones((J,), jnp.float32), keys=keys,
            clip_norm=pol.clip_norm, noise_multiplier=pol.noise_multiplier)
        want = self._policy_rows(pol, x, round_key, t)
        _exact(got, want)

    def test_different_rounds_different_noise(self):
        pol = PrivacyPolicy(clip_norm=0.7, noise_multiplier=1.3)
        x = _mat((3, 16))
        outs = []
        for rk in (jax.random.PRNGKey(0), jax.random.PRNGKey(1)):
            keys = jax.vmap(
                lambda s: jax.random.fold_in(pol.upload_key(rk, 0, s), 0)
            )(jnp.arange(3))
            outs.append(ops.wire_upload(
                x, jnp.ones((3,)), keys=keys, clip_norm=0.7,
                noise_multiplier=1.3))
        assert not np.array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


# ---------------------------------------------------------------------------
# fused combine: masked / weighted (trimmed) mean + in-kernel dequant
# ---------------------------------------------------------------------------

WEIGHT_PATTERNS = ["ones", "binary", "fractional", "subunit", "zero"]


def _weights(J, pattern, salt=0):
    k = jax.random.fold_in(KEY, 300 + salt)
    if pattern == "ones":
        return jnp.ones((J,), jnp.float32)
    if pattern == "binary":
        return jax.random.bernoulli(k, 0.6, (J,)).astype(jnp.float32)
    if pattern == "fractional":
        return jax.random.uniform(k, (J,), jnp.float32, 0.0, 1.0)
    if pattern == "subunit":  # async decayed weights summing below 1
        return jax.random.uniform(k, (J,), jnp.float32, 0.0, 1.0) / (2.0 * J)
    return jnp.zeros((J,), jnp.float32)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("pattern", WEIGHT_PATTERNS)
@pytest.mark.parametrize("trim", [None, 0.1, 0.25, 0.49])
def test_combine_matches_oracle_and_aggregator(shape, pattern, trim):
    J, P = shape
    x = _mat((J, P), salt=J * 31 + P)
    w = _weights(J, pattern, salt=J + P)
    got = ops.wire_combine(x, w, trim_frac=trim)
    if trim is None:
        want = jax.jit(ref.masked_weighted_mean_ref)(x, w)
        agg = MeanAggregator()
    else:
        want = jax.jit(functools.partial(
            ref.masked_trimmed_mean_ref, trim_frac=trim))(x, w)
        agg = TrimmedMeanAggregator(trim_frac=trim)
    _exact(got, want)
    live = jax.jit(agg.combine)(x, w)
    _exact(got, live)


@pytest.mark.parametrize("trim", [None, 0.2])
def test_combine_int8_dequant_in_kernel(trim):
    """scales= fuses dequant into the same pass: equals dequantizing to a
    materialized f32 matrix first."""
    y = 3.0 * _mat((6, 130))
    scale = jnp.max(jnp.abs(y), axis=1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(y / scale[:, None]), -127, 127).astype(jnp.int8)
    w = _weights(6, "fractional")
    got = ops.wire_combine(q, w, scales=scale, trim_frac=trim)
    dense = jax.jit(ref.int8_rows_dequant_ref)(q, scale)
    want = ops.wire_combine(dense, w, trim_frac=trim)
    _exact(got, want)


def test_combine_block_cols_invariance():
    x = _mat((5, 120))
    w = _weights(5, "fractional")
    outs = [ops.wire_combine(x, w, trim_frac=0.2, block_cols=bc)
            for bc in (1, 8, 120)]
    _exact(outs[0], outs[1])
    _exact(outs[0], outs[2])


def test_combine_scales_require_int8():
    with pytest.raises(ValueError):
        ops.wire_combine(_mat((3, 4)), jnp.ones((3,)),
                         scales=jnp.ones((3,)))


# ---------------------------------------------------------------------------
# fused Newton–Schulz sqrt
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [1, 2, 3, 8, 16])
@pytest.mark.parametrize("iters", [5, 25])
def test_sqrtm_matches_core_and_ref(d, iters):
    a = _mat((d, d), salt=d)
    mat = a @ a.T + 0.1 * jnp.eye(d)
    got = ops.sqrtm_ns(mat, num_iters=iters)
    core = jax.jit(functools.partial(
        sqrtm_newton_schulz, num_iters=iters))(mat)
    oracle = jax.jit(functools.partial(
        ref.newton_schulz_sqrtm_ref, num_iters=iters))(mat)
    _exact(got, core)
    _exact(got, oracle)


def test_sqrtm_is_a_sqrt():
    a = _mat((6, 6), salt=99)
    mat = a @ a.T + 0.5 * jnp.eye(6)
    s = ops.sqrtm_ns(mat, num_iters=30)
    np.testing.assert_allclose(np.asarray(s @ s), np.asarray(mat),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# property sweeps (hypothesis when present, fixed seeded grid otherwise)
# ---------------------------------------------------------------------------

def _check_random_case(J, P, trim_i, pattern_i):
    x = _mat((J, P), salt=J * 7919 + P)
    trim = (None, 0.1, 0.3)[trim_i]
    pattern = WEIGHT_PATTERNS[pattern_i]
    w = _weights(J, pattern, salt=J ^ P)
    got = ops.wire_combine(x, w, trim_frac=trim)
    if trim is None:
        want = jax.jit(ref.masked_weighted_mean_ref)(x, w)
    else:
        want = jax.jit(functools.partial(
            ref.masked_trimmed_mean_ref, trim_frac=trim))(x, w)
    _exact(got, want)
    mask = (w > 0).astype(jnp.float32)
    up = ops.wire_upload(x, mask, keys=_keys(J, salt=P),
                         clip_norm=0.6, noise_multiplier=0.8, quantize=True)
    oracle = jax.jit(functools.partial(
        ref.wire_upload_ref, clip_norm=0.6, noise_multiplier=0.8,
        quantize=True))
    _exact(up, oracle(x, mask=mask, keys=_keys(J, salt=P)))


if HAVE_HYPOTHESIS:
    @given(J=st.integers(1, 17), P=st.integers(1, 300),
           trim_i=st.integers(0, 2), pattern_i=st.integers(0, 4))
    @settings(max_examples=25, deadline=None)
    def test_wire_kernels_property(J, P, trim_i, pattern_i):
        _check_random_case(J, P, trim_i, pattern_i)
else:
    _rng = np.random.default_rng(515151)
    _CASES = [(int(j), int(p), int(t), int(m)) for j, p, t, m in zip(
        _rng.integers(1, 18, 12), _rng.integers(1, 301, 12),
        _rng.integers(0, 3, 12), _rng.integers(0, 5, 12), strict=True)]

    @pytest.mark.parametrize("J,P,trim_i,pattern_i", _CASES)
    def test_wire_kernels_property(J, P, trim_i, pattern_i):
        _check_random_case(J, P, trim_i, pattern_i)
