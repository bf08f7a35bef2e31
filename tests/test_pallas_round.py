"""Tests for the fused Pallas pull-round kernel (ops/pallas_round.py).

CPU strategy: the Mosaic interpreter stubs the hardware PRNG with zeros
(test_pallas.py round-1 finding), so kernel MATH is tested by injecting
known random bits (``inject_bits``) and checking against an independent
numpy model of the documented sampling scheme.  Statistical properties of
the hardware PRNG path (curve shape, determinism, seed sensitivity) are
TPU-only tests.

Reference semantics being modelled: the batched pull form of the
reference's broadcast relay (/root/reference/main.go:72-88) — every node
asks a uniformly random partner for its digest each round.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_tpu.ops.pallas_round import (
    BITS, LANES, FusedState, compiled_until_fused,
    compiled_until_fused_multirumor, coverage_node_packed, coverage_words,
    fused_multirumor_pull_round, fused_pull_round, init_fused_state,
    init_multirumor_state, mr_rows, n_rows, node_pack, node_unpack,
    word_pack, word_unpack)

ON_TPU = jax.default_backend() == "tpu"


def numpy_reference_round(table, sbits, rbits, n, fanout, sharing=1):
    """Independent model of the kernel's documented sampling scheme
    (``sharing=2``: a plane pair splits one draw's disjoint 12-bit
    fields — the round-5 PRNG-harvest variant)."""
    rows = table.shape[0]
    s = (sbits[0, :].astype(np.uint64) % rows).astype(np.int64)   # [128]
    # rot[i, j] = table[(i - s_j) mod rows, j]
    i = np.arange(rows)[:, None]
    rot = table[(i - s[None, :]) % rows, np.arange(LANES)[None, :]]
    acc = table.copy()
    for k in range(0, BITS, sharing):
        for f in range(fanout):
            rb = rbits[(k // sharing) * fanout + f]
            for j in range(sharing):
                m = (rb >> (12 * j)) & (LANES - 1)
                c = (rb >> (12 * j + 7)) & (BITS - 1)
                partner = np.take_along_axis(rot, m.astype(np.int64),
                                             axis=1)
                bit = (partner >> c) & 1
                acc = acc | (bit.astype(np.uint32) << np.uint32(k + j))
    # phantom masking
    flat = acc.reshape(-1)
    n_valid_words = -(-n // BITS)
    tail = n % BITS
    out = flat.copy()
    out[n_valid_words:] = 0
    if tail:
        out[n_valid_words - 1] &= np.uint32((1 << tail) - 1)
    return out.reshape(rows, LANES)


def _random_bits(rng, rows, fanout, sharing=1):
    """Injected-bit buffers at the kernel's contract shapes — the ONE
    place the (sbits, rbits) layout lives (``sharing`` divides the rbits
    draw count: a plane pair shares one word)."""
    sbits = rng.integers(0, 2**32, size=(8, LANES), dtype=np.uint32)
    rbits = rng.integers(0, 2**32,
                         size=(fanout * BITS // sharing, rows, LANES),
                         dtype=np.uint32)
    return sbits, rbits


@pytest.mark.parametrize("n,fanout,sharing",
                         [(4096 * 8, 1, 1), (4096 * 8 - 37, 1, 1),
                          (4096 * 16, 2, 1),
                          (4096 * 8, 1, 2), (4096 * 8 - 37, 2, 2)])
def test_kernel_math_matches_numpy_model(n, fanout, sharing):
    rng = np.random.default_rng(42 + n + fanout + sharing)
    rows = n_rows(n)
    infected = rng.random(n) < 0.03
    table = np.asarray(node_pack(jnp.asarray(infected)))
    sbits, rbits = _random_bits(rng, rows, fanout, sharing)
    got = fused_pull_round(jnp.asarray(table), 0, 0, n, fanout,
                           interpret=not ON_TPU,
                           inject_bits=(sbits, rbits),
                           plane_sharing=sharing)
    want = numpy_reference_round(table, sbits, rbits, n, fanout, sharing)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_plane_sharing_validation():
    t = init_fused_state(4096 * 8).table
    with pytest.raises(ValueError, match="plane_sharing"):
        fused_pull_round(t, 0, 0, 4096 * 8, 1, interpret=not ON_TPU,
                         plane_sharing=3)
    with pytest.raises(ValueError, match="drop coin"):
        fused_pull_round(t, 0, 0, 4096 * 8, 1, interpret=not ON_TPU,
                         drop_threshold=1000, plane_sharing=2)
    # still loud with the threshold as a runtime operand: a partition
    # side mask overlaps the pair split the same way the drop coin does
    from gossip_tpu.ops.pallas_round import render_cut_bits
    with pytest.raises(ValueError, match="drop coin"):
        fused_pull_round(t, 0, 0, 4096 * 8, 1, interpret=not ON_TPU,
                         cut_words=render_cut_bits(64, 4096 * 8),
                         plane_sharing=2)
    # a TRACED threshold cannot be proven zero at trace time — rejected
    # outright (a silently correlated drop stream would be worse)
    with pytest.raises(ValueError, match="traced"):
        fused_pull_round(t, 0, 0, 4096 * 8, 1, interpret=not ON_TPU,
                         drop_threshold=jnp.int32(104858),
                         plane_sharing=2)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for n in (50, 4096 * 8, 4096 * 8 + 1, 60000):
        inf = rng.random(n) < 0.3
        tab = node_pack(jnp.asarray(inf))
        back = np.asarray(node_unpack(tab, n))
        np.testing.assert_array_equal(back, inf)
        cov = float(coverage_node_packed(tab, n))
        assert abs(cov - inf.mean()) < 1e-6


def test_pull_is_monotone_and_phantoms_stay_zero():
    n = 4096 * 8 - 123
    rng = np.random.default_rng(1)
    rows = n_rows(n)
    inf = rng.random(n) < 0.1
    table = node_pack(jnp.asarray(inf))
    sbits, rbits = _random_bits(rng, rows, 1)
    out = np.asarray(fused_pull_round(table, 0, 0, n, 1,
                                      interpret=not ON_TPU,
                                      inject_bits=(sbits, rbits)))
    before = np.asarray(node_unpack(table, n))
    after = np.asarray(node_unpack(jnp.asarray(out), n))
    assert (after | before == after).all(), "pull must be monotone"
    n_valid_words = -(-n // BITS)
    flat = out.reshape(-1)
    assert not flat[n_valid_words:].any()
    tail = n % BITS
    if tail:
        assert flat[n_valid_words - 1] < (1 << tail)


def test_injected_uniform_bits_track_mean_field():
    """With good injected bits the coverage recurrence c' = 1-(1-c)^2
    (every node pulls one uniform partner) must hold to a few percent."""
    n = 4096 * 32
    rows = n_rows(n)
    rng = np.random.default_rng(7)
    cov = 0.2
    inf = rng.random(n) < cov
    table = node_pack(jnp.asarray(inf))
    sbits, rbits = _random_bits(rng, rows, 1)
    out = fused_pull_round(table, 0, 0, n, 1, interpret=not ON_TPU,
                           inject_bits=(sbits, rbits))
    got = float(coverage_node_packed(out, n))
    c = inf.mean()
    want = 1 - (1 - c) ** 2
    assert abs(got - want) < 0.02, (got, want)


# ---- multi-rumor (one-word-per-node) kernel -------------------------------

def numpy_mr_round(table, sbits, rbits, n, fanout):
    """Independent model of the multi-rumor kernel's sampling scheme."""
    rows = table.shape[0]
    acc = table.copy()
    for f in range(fanout):
        s = (sbits[f, 0, :].astype(np.uint64) % rows).astype(np.int64)
        i = np.arange(rows)[:, None]
        rot = table[(i - s[None, :]) % rows, np.arange(LANES)[None, :]]
        m = rbits[f] & (LANES - 1)
        acc = acc | np.take_along_axis(rot, m.astype(np.int64), axis=1)
    flat = acc.reshape(-1)
    flat[n:] = 0
    return flat.reshape(rows, LANES)


def _mr_bits(rng, rows, fanout):
    sbits = rng.integers(0, 2**32, size=(fanout, 8, LANES), dtype=np.uint32)
    rbits = rng.integers(0, 2**32, size=(fanout, rows, LANES),
                         dtype=np.uint32)
    return sbits, rbits


@pytest.mark.parametrize("n,r,fanout", [(128 * 16, 8, 1),
                                        (128 * 16 - 29, 32, 1),
                                        (128 * 24, 3, 2)])
def test_mr_kernel_math_matches_numpy_model(n, r, fanout):
    rng = np.random.default_rng(5 + n + r)
    rows = mr_rows(n)
    seen = rng.random((n, r)) < 0.05
    table = np.asarray(word_pack(jnp.asarray(seen)))
    sbits, rbits = _mr_bits(rng, rows, fanout)
    got = fused_multirumor_pull_round(jnp.asarray(table), 0, 0, n, fanout,
                                      interpret=not ON_TPU,
                                      inject_bits=(sbits, rbits))
    want = numpy_mr_round(table, sbits, rbits, n, fanout)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_mr_pack_roundtrip_and_coverage():
    rng = np.random.default_rng(3)
    for n, r in ((200, 5), (128 * 16 + 1, 32), (5000, 1)):
        seen = rng.random((n, r)) < 0.3
        tab = word_pack(jnp.asarray(seen))
        np.testing.assert_array_equal(np.asarray(word_unpack(tab, n, r)),
                                      seen)
        cov = float(coverage_words(tab, n, r))
        assert abs(cov - seen.mean(axis=0).min()) < 1e-6
    with pytest.raises(ValueError, match="rumors"):
        word_pack(jnp.zeros((64, 33), bool))


def test_mr_all_rumors_share_one_partner_per_draw():
    """A pull moves the partner's WHOLE word: wherever rumor 0 was newly
    received, every rumor the partner held must arrive with it."""
    n, r = 128 * 16, 7
    rng = np.random.default_rng(9)
    rows = mr_rows(n)
    # partner candidates hold either ALL rumors or none
    holders = rng.random(n) < 0.1
    seen = np.repeat(holders[:, None], r, axis=1)
    table = word_pack(jnp.asarray(seen))
    sbits, rbits = _mr_bits(rng, rows, 1)
    out = np.asarray(fused_multirumor_pull_round(
        table, 0, 0, n, 1, interpret=not ON_TPU,
        inject_bits=(sbits, rbits)))
    got = np.asarray(word_unpack(jnp.asarray(out), n, r))
    # every node's row is all-True or all-False: digests moved atomically
    assert (got.all(axis=1) | (~got.any(axis=1))).all()


def test_mr_injected_bits_track_mean_field():
    n, r = 128 * 64, 8
    rows = mr_rows(n)
    rng = np.random.default_rng(11)
    seen = rng.random((n, r)) < 0.2
    table = word_pack(jnp.asarray(seen))
    sbits, rbits = _mr_bits(rng, rows, 1)
    out = fused_multirumor_pull_round(table, 0, 0, n, 1,
                                      interpret=not ON_TPU,
                                      inject_bits=(sbits, rbits))
    got = float(coverage_words(out, n, r))
    c = 0.2
    want = 1 - (1 - c) ** 2
    assert abs(got - want) < 0.03, (got, want)


@pytest.mark.skipif(not ON_TPU, reason="hw PRNG path needs a real TPU "
                    "(interpreter stubs prng_random_bits with zeros)")
class TestHardwarePRNGMultirumor:
    def test_deterministic_and_stream_distinct(self):
        n, r = 128 * 64, 8
        st = init_multirumor_state(n, r)
        a = fused_multirumor_pull_round(st.table, 3, 5, n)
        b = fused_multirumor_pull_round(init_multirumor_state(n, r).table,
                                        3, 5, n)
        assert jnp.array_equal(a, b)
        c = fused_multirumor_pull_round(init_multirumor_state(n, r).table,
                                        3, 6, n)
        assert not jnp.array_equal(a, c)

    def test_mr_curve_matches_mean_field(self):
        n, r = 1 << 18, 8
        loop, init = compiled_until_fused_multirumor(n, r, seed=0,
                                                     max_rounds=64)
        final = loop(init)
        got = int(final.round)
        c, want = 1.0 / n, 0
        while c < 0.99:
            c = 1 - (1 - c) ** 2
            want += 1
        # min-over-rumors lags single-rumor coverage by a round or two
        assert want - 1 <= got <= want + 4, (got, want)
        assert float(coverage_words(final.table, n, r)) >= 0.99


@pytest.mark.skipif(not ON_TPU, reason="hw PRNG path needs a real TPU "
                    "(interpreter stubs prng_random_bits with zeros)")
class TestHardwarePRNG:
    def test_deterministic_same_seed_and_round(self):
        n = 4096 * 16
        st = init_fused_state(n)
        a = fused_pull_round(st.table, 3, 5, n)
        b = fused_pull_round(init_fused_state(n).table, 3, 5, n)
        assert jnp.array_equal(a, b)

    def test_round_and_seed_vary_the_draw(self):
        n = 4096 * 16
        rng = np.random.default_rng(2)
        inf = jnp.asarray(rng.random(n) < 0.2)
        tab = node_pack(inf)
        a = fused_pull_round(tab, 3, 5, n)
        b = fused_pull_round(node_pack(inf), 3, 6, n)
        c = fused_pull_round(node_pack(inf), 4, 5, n)
        assert not jnp.array_equal(a, b)
        assert not jnp.array_equal(a, c)

    def test_curve_matches_mean_field_trajectory(self):
        """rounds-to-99% at N=2^18 must match the mean-field recurrence
        (c' = 1-(1-c)^2 from c0=1/N) within +/-3 rounds, like the threefry
        pull path does."""
        n = 1 << 18
        loop, init = compiled_until_fused(n, seed=0, max_rounds=64)
        final = loop(init)
        got = int(final.round)
        c, want = 1.0 / n, 0
        while c < 0.99:
            c = 1 - (1 - c) ** 2
            want += 1
        assert abs(got - want) <= 3, (got, want)
        assert float(coverage_node_packed(final.table, n)) >= 0.99

    def test_fanout_two_converges_faster(self):
        n = 1 << 18
        l1, i1 = compiled_until_fused(n, seed=1, fanout=1, max_rounds=64)
        l2, i2 = compiled_until_fused(n, seed=1, fanout=2, max_rounds=64)
        r1 = int(l1(i1).round)
        r2 = int(l2(i2).round)
        assert r2 < r1


@pytest.mark.parametrize("n", [128 * 16, 128 * 24 - 37])
def test_mr_staged_big_path_bitwise_matches_value_kernel(n):
    """The staged big-table path (XLA rotation + grid-blocked gather —
    the route for tables past the VMEM envelope, e.g. 10M x 32 rumors)
    computes the SAME function as the value kernel: bitwise-equal on
    identical injected bits, including phantom masking at ragged n."""
    from gossip_tpu.ops.pallas_round import _fused_mr_round_big, _mr_wants_big
    rng = np.random.default_rng(11 + n)
    rows = mr_rows(n)
    seen = rng.random((n, 32)) < 0.03
    table = jnp.asarray(np.asarray(word_pack(jnp.asarray(seen))))
    sbits, rbits = _mr_bits(rng, rows, 1)
    want = fused_multirumor_pull_round(table, 0, 0, n, 1,
                                       interpret=not ON_TPU,
                                       inject_bits=(sbits, rbits))
    got = _fused_mr_round_big(table, 0, 0, n, not ON_TPU, (sbits, rbits))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # fanout 2 (round 5): multi-pass accumulation must still compute the
    # value kernel's function bitwise on identical injected bits
    sbits2, rbits2 = _mr_bits(rng, rows, 2)
    want2 = fused_multirumor_pull_round(table, 0, 0, n, 2,
                                        interpret=not ON_TPU,
                                        inject_bits=(sbits2, rbits2))
    got2 = _fused_mr_round_big(table, 0, 0, n, not ON_TPU,
                               (sbits2, rbits2), fanout=2)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))
    # routing: any over-VMEM table picks the big path regardless of
    # fanout (round 5); small tables stay on the value kernel
    assert _mr_wants_big(mr_rows(10_000_000) * LANES * 4, 1)
    assert _mr_wants_big(mr_rows(10_000_000) * LANES * 4, 2)
    assert not _mr_wants_big(mr_rows(1_000_000) * LANES * 4, 1)


def test_mr_staged_big_path_multiblock_grid(monkeypatch):
    """Exercise the staged path's block-indexed code — the node_id block
    offset, the per-block rbits BlockSpec index map, and a RAGGED final
    block (rows not a multiple of the block) — by shrinking the block so
    the grid has several steps, as it does at the 10M flagship
    (78128 rows / 1024-row blocks)."""
    import gossip_tpu.ops.pallas_round as PR
    monkeypatch.setattr(PR, "_MR_GATHER_BLOCK", 16)
    rng = np.random.default_rng(23)
    rows = 40                               # 2 full blocks + ragged 8
    n = rows * LANES - 13
    seen = rng.random((n, 32)) < 0.03
    table = jnp.asarray(np.asarray(word_pack(jnp.asarray(seen))))
    sbits, rbits = _mr_bits(rng, rows, 1)
    want = fused_multirumor_pull_round(table, 0, 0, n, 1,
                                       interpret=not ON_TPU,
                                       inject_bits=(sbits, rbits))
    got = PR._fused_mr_round_big(table, 0, 0, n, not ON_TPU, (sbits, rbits))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.skipif(not ON_TPU, reason="hw PRNG path needs a real TPU "
                    "(interpreter stubs prng_random_bits with zeros)")
class TestHardwarePRNGStagedBigPath:
    """Statistical checks of the STAGED big-table path's hw-PRNG scheme —
    the per-block seed fold is new code with its own randomness shape
    (one stream per grid block instead of one (rows,128) draw)."""

    def test_block_streams_are_distinct(self):
        """All-rows-identical table: the rotation is a no-op and each
        output block is a pure function of its own block's lane draws —
        if the per-block seed fold degenerated (same stream per block),
        block outputs would repeat with the grid period."""
        from gossip_tpu.ops.pallas_round import (_MR_GATHER_BLOCK,
                                                 _fused_mr_round_big)
        rows = 4 * _MR_GATHER_BLOCK              # 4 exact grid blocks
        n = rows * LANES
        rng = np.random.default_rng(0)
        row = rng.integers(0, 2**32, size=(1, LANES), dtype=np.uint32)
        table = jnp.asarray(np.repeat(row, rows, axis=0))
        out = np.asarray(_fused_mr_round_big(table, 0, 1, n, False, None))
        blocks = out.reshape(4, _MR_GATHER_BLOCK, LANES)
        assert not np.array_equal(blocks[0], blocks[1])
        assert not np.array_equal(blocks[1], blocks[2])
        assert not np.array_equal(blocks[2], blocks[3])
        # determinism on the same (seed, round)
        out2 = np.asarray(_fused_mr_round_big(table, 0, 1, n, False, None))
        np.testing.assert_array_equal(out, out2)
        # distinct stream on the next round
        out3 = np.asarray(_fused_mr_round_big(table, 0, 2, n, False, None))
        assert not np.array_equal(out, out3)

    def test_big_path_growth_at_flagship_scale(self):
        """12 rounds at N=10M x 32 rumors through the real routing
        (fused_multirumor_pull_round picks the staged path): per-rumor
        populations must grow ~2x/round once past branching noise."""
        from gossip_tpu.ops.pallas_round import (_mr_wants_big,
                                                 fused_table_bytes)
        n = 10_000_000
        assert _mr_wants_big(fused_table_bytes(n, 32), 1)   # routing sanity
        st = init_multirumor_state(n, 32)
        out = st.table
        for r in range(1, 13):
            out = fused_multirumor_pull_round(out, jnp.int32(0),
                                              jnp.int32(r), n, 1)
        flat = np.asarray(out).reshape(-1)[:n]
        counts = np.array([int(((flat >> k) & np.uint32(1)).sum())
                           for k in range(32)])
        # mean over 32 independent rumors after 12 doublings from 1:
        # E ~ 2^12; branching variance is tamed by averaging the rumors
        assert 2**10 <= counts.mean() <= 2**14
        assert (counts > 0).all()


# ---------------------------------------------------------------------------
# Fault masks (round 4): static alive bitmap + 20-bit drop threshold in the
# single-rumor fused kernel.  Same CPU strategy as above — injected bits,
# independent numpy model, exact equality.

def numpy_fault_round(table, sbits, rbits, n, fanout, drop_threshold,
                      alive_table):
    """numpy_reference_round + the documented fault-mask semantics:
    dead nodes cleared from the rotation SOURCE (serve nothing) and from
    plane contributions (acquire nothing); a pull whose draw's bits
    12..31 fall below drop_threshold is dropped."""
    rows = table.shape[0]
    s = (sbits[0, :].astype(np.uint64) % rows).astype(np.int64)
    i = np.arange(rows)[:, None]
    src = table & alive_table if alive_table is not None else table
    rot = src[(i - s[None, :]) % rows, np.arange(LANES)[None, :]]
    acc = table.copy()
    for k in range(BITS):
        for f in range(fanout):
            rb = rbits[k * fanout + f]
            m = rb & (LANES - 1)
            c = (rb >> 7) & (BITS - 1)
            partner = np.take_along_axis(rot, m.astype(np.int64), axis=1)
            bit = ((partner >> c) & 1).astype(np.uint32)
            if drop_threshold:
                bit = np.where((rb >> 12) >= drop_threshold, bit,
                               np.uint32(0))
            if alive_table is not None:
                bit = bit & ((alive_table >> np.uint32(k)) & 1)
            acc = acc | (bit << np.uint32(k))
    flat = acc.reshape(-1)
    n_valid_words = -(-n // BITS)
    tail = n % BITS
    out = flat.copy()
    out[n_valid_words:] = 0
    if tail:
        out[n_valid_words - 1] &= np.uint32((1 << tail) - 1)
    return out.reshape(rows, LANES)


@pytest.mark.parametrize("drop_p,death", [(0.3, 0.0), (0.0, 0.25),
                                          (0.2, 0.2)])
def test_kernel_fault_masks_match_numpy_model(drop_p, death):
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import fault_masks_node_packed
    n, fanout = 4096 * 8 - 37, 1
    rng = np.random.default_rng(97)
    rows = n_rows(n)
    infected = rng.random(n) < 0.05
    table = np.asarray(node_pack(jnp.asarray(infected)))
    fault = FaultConfig(drop_prob=drop_p, node_death_rate=death, seed=3)
    alive_tab, thresh = fault_masks_node_packed(fault, n, origin=0)
    alive_np = None if alive_tab is None else np.asarray(alive_tab)
    assert (thresh > 0) == (drop_p > 0)
    assert (alive_np is not None) == (death > 0)
    sbits, rbits = _random_bits(rng, rows, fanout)
    got = fused_pull_round(jnp.asarray(table), 0, 0, n, fanout,
                           interpret=not ON_TPU,
                           inject_bits=(sbits, rbits),
                           drop_threshold=thresh,
                           alive_table=alive_tab)
    want = numpy_fault_round(table, sbits, rbits, n, fanout, thresh,
                             alive_np)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_fault_free_path_unchanged_by_fault_args():
    """drop_threshold=0 + alive_table=None must be EXACTLY the round-2
    kernel: the flagship bench lowering cannot shift under the fault
    feature."""
    n, fanout = 4096 * 8, 1
    rng = np.random.default_rng(5)
    rows = n_rows(n)
    table = np.asarray(node_pack(jnp.asarray(rng.random(n) < 0.05)))
    sbits, rbits = _random_bits(rng, rows, fanout)
    a = fused_pull_round(jnp.asarray(table), 0, 0, n, fanout,
                         interpret=not ON_TPU, inject_bits=(sbits, rbits))
    b = fused_pull_round(jnp.asarray(table), 0, 0, n, fanout,
                         interpret=not ON_TPU, inject_bits=(sbits, rbits),
                         drop_threshold=0, alive_table=None)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compiled_until_fused_fault_semantics():
    """Driver-level contract on the CPU interpreter.  The stubbed PRNG
    draws zeros -> no rotation, and every word (row i, lane j, plane k)
    pulls bit 0 of word (i, 0): the only initially-infected such source
    is the origin (node 0), so the epidemic's deterministic fixed point
    is "every ALIVE node of row 0" — enough structure to pin the mask
    semantics exactly.  A drop_threshold of 2^20 (drop everything)
    freezes the epidemic entirely."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import NODES_PER_ROW
    n = 4096 * 8
    fault = FaultConfig(node_death_rate=0.3, seed=11)
    loop, init = compiled_until_fused(n, seed=0, max_rounds=3,
                                      interpret=True, fault=fault)
    final = loop(init)
    from gossip_tpu.models.state import alive_mask
    alive = np.asarray(alive_mask(fault, n, 0))
    inf = np.asarray(node_unpack(final.table, n))
    assert not np.any(inf & ~alive), "a dead node acquired infection"
    want = alive & (np.arange(n) < NODES_PER_ROW)   # row 0, alive only
    np.testing.assert_array_equal(inf, want)
    assert int(final.round) == 3                    # fixed point < target

    # drop everything: nothing ever spreads
    frozen = FaultConfig(drop_prob=1.0, seed=1)
    loop2, init2 = compiled_until_fused(n, seed=0, max_rounds=3,
                                        interpret=True, fault=frozen)
    final2 = loop2(init2)
    assert float(coverage_node_packed(final2.table, n)) * n == 1.0
    assert int(final2.round) == 3


@pytest.mark.skipif(not ON_TPU, reason="hw PRNG path needs a real TPU "
                                       "(interpreter stubs random bits)")
class TestHardwarePRNGFaultMasks:
    def test_dead_stay_dark_and_drop_slows_convergence(self):
        """Fault masks under the REAL hardware PRNG: dead nodes never
        acquire infection over a full epidemic, the alive-weighted
        epidemic still completes, and a heavy drop rate costs extra
        rounds vs the fault-free run (statistical, wide margin)."""
        from gossip_tpu.config import FaultConfig
        from gossip_tpu.models.state import alive_mask
        from gossip_tpu.ops.pallas_round import (
            coverage_node_packed_alive, fault_masks_node_packed)
        n = 1 << 18
        fault = FaultConfig(node_death_rate=0.2, seed=7)
        loop, init = compiled_until_fused(n, seed=3, max_rounds=64,
                                          fault=fault)
        final = loop(init)
        alive = np.asarray(alive_mask(fault, n, 0))
        inf = np.asarray(node_unpack(final.table, n))
        assert not np.any(inf & ~alive)
        alive_tab, _ = fault_masks_node_packed(fault, n, 0)
        assert float(coverage_node_packed_alive(final.table,
                                                alive_tab)) >= 0.99
        l0, i0 = compiled_until_fused(n, seed=3, max_rounds=64)
        r0 = int(l0(i0).round)
        drop = FaultConfig(drop_prob=0.5, seed=2)
        ld, idr = compiled_until_fused(n, seed=3, max_rounds=64,
                                       fault=drop)
        rd = int(ld(idr).round)
        assert rd > r0, (rd, r0)    # half the pulls dropped: more rounds


def numpy_mr_fault_round(table, sbits, rbits, n, fanout, drop_threshold,
                         alive_words):
    """numpy_mr_round + the word-layout fault-mask semantics."""
    rows = table.shape[0]
    src = table & alive_words if alive_words is not None else table
    acc = table.copy()
    for f in range(fanout):
        s = (sbits[f, 0, :].astype(np.uint64) % rows).astype(np.int64)
        i = np.arange(rows)[:, None]
        rot = src[(i - s[None, :]) % rows, np.arange(LANES)[None, :]]
        rb = rbits[f]
        m = rb & (LANES - 1)
        partner = np.take_along_axis(rot, m.astype(np.int64), axis=1)
        if drop_threshold:
            partner = np.where((rb >> 12) >= drop_threshold, partner,
                               np.uint32(0))
        if alive_words is not None:
            partner = partner & alive_words
        acc = acc | partner
    flat = acc.reshape(-1)
    flat[n:] = 0
    return flat.reshape(rows, LANES)


@pytest.mark.parametrize("drop_p,death,fanout", [(0.4, 0.0, 2),
                                                 (0.0, 0.3, 1),
                                                 (0.25, 0.15, 1)])
def test_mr_kernel_fault_masks_match_numpy_model(drop_p, death, fanout):
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import fault_masks_word
    n, r = 128 * 16 - 29, 8
    rng = np.random.default_rng(31)
    rows = mr_rows(n)
    seen = rng.random((n, r)) < 0.06
    table = np.asarray(word_pack(jnp.asarray(seen)))
    fault = FaultConfig(drop_prob=drop_p, node_death_rate=death, seed=5)
    alive_words, thresh = fault_masks_word(fault, n, origin=0)
    alive_np = None if alive_words is None else np.asarray(alive_words)
    sbits, rbits = _mr_bits(rng, rows, fanout)
    got = fused_multirumor_pull_round(jnp.asarray(table), 0, 0, n, fanout,
                                      interpret=not ON_TPU,
                                      inject_bits=(sbits, rbits),
                                      drop_threshold=thresh,
                                      alive_words=alive_words)
    want = numpy_mr_fault_round(table, sbits, rbits, n, fanout, thresh,
                                alive_np)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_mr_staged_big_path_fault_masks_match_value_kernel():
    """Both MR routes implement the SAME faulted function: bitwise-equal
    on identical injected bits with the alive + drop masks on."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import (_fused_mr_round_big,
                                             fault_masks_word)
    n = 128 * 16 - 29
    rng = np.random.default_rng(13)
    rows = mr_rows(n)
    seen = rng.random((n, 32)) < 0.04
    table = jnp.asarray(np.asarray(word_pack(jnp.asarray(seen))))
    fault = FaultConfig(drop_prob=0.3, node_death_rate=0.2, seed=9)
    alive_words, thresh = fault_masks_word(fault, n, origin=0)
    sbits, rbits = _mr_bits(rng, rows, 1)
    want = fused_multirumor_pull_round(table, 0, 0, n, 1,
                                       interpret=not ON_TPU,
                                       inject_bits=(sbits, rbits),
                                       drop_threshold=thresh,
                                       alive_words=alive_words)
    got = _fused_mr_round_big(table, 0, 0, n, not ON_TPU, (sbits, rbits),
                              drop_threshold=thresh,
                              alive_words=alive_words)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mr_fault_free_path_unchanged_by_fault_args():
    n, r = 128 * 16, 8
    rng = np.random.default_rng(8)
    rows = mr_rows(n)
    table = jnp.asarray(np.asarray(word_pack(
        jnp.asarray(rng.random((n, r)) < 0.05))))
    sbits, rbits = _mr_bits(rng, rows, 1)
    a = fused_multirumor_pull_round(table, 0, 0, n, 1,
                                    interpret=not ON_TPU,
                                    inject_bits=(sbits, rbits))
    b = fused_multirumor_pull_round(table, 0, 0, n, 1,
                                    interpret=not ON_TPU,
                                    inject_bits=(sbits, rbits),
                                    drop_threshold=0, alive_words=None)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_coverage_words_alive_weighting():
    """Alive-weighted MR coverage: dead nodes leave the denominator and
    their rumor bits stop counting."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import (coverage_words_alive,
                                             fault_masks_word)
    from gossip_tpu.models.state import alive_mask
    n, r = 500, 4
    rng = np.random.default_rng(2)
    seen = rng.random((n, r)) < 0.5
    fault = FaultConfig(node_death_rate=0.3, seed=6)
    alive = np.asarray(alive_mask(fault, n, 0))
    alive_words, _ = fault_masks_word(fault, n, 0)
    got = float(coverage_words_alive(word_pack(jnp.asarray(seen)),
                                     alive_words, r))
    want = (seen[alive].mean(axis=0)).min()
    assert got == pytest.approx(want, abs=1e-6)


@pytest.mark.skipif(not ON_TPU, reason="hw PRNG path needs a real TPU "
                                       "(interpreter stubs random bits)")
class TestHardwarePRNGFaultMasksMultirumor:
    def test_mr_dead_stay_dark_under_hw_prng(self):
        """Per-rumor contract: a rumor whose origin survives the death
        draw floods the alive population; a rumor whose origin is dead
        never spreads (rumor.py's documented SI property) — and no dead
        node ever holds any rumor.  Only the loop's max_rounds drives
        the run (the min-over-rumors cond can't reach target when any
        origin is dead, which the alive draw here includes on
        purpose)."""
        from gossip_tpu.config import FaultConfig
        from gossip_tpu.models.state import alive_mask
        from gossip_tpu.ops.pallas_round import (
            compiled_until_fused_multirumor, word_unpack)
        n, r = 1 << 16, 8
        fault = FaultConfig(node_death_rate=0.2, drop_prob=0.1, seed=4)
        loop, init = compiled_until_fused_multirumor(n, r, seed=5,
                                                     max_rounds=48,
                                                     fault=fault)
        final = loop(init)
        alive = np.asarray(alive_mask(fault, n, 0))
        seen = np.asarray(word_unpack(final.table, n, r))
        # dead nodes ACQUIRE nothing, but their own state stays put
        # (kernel contract: acc starts from the table) — so a dead
        # ORIGIN keeps exactly its own seeded bit; every other dead
        # node holds nothing
        dead_ids = np.arange(n)[~alive]
        expect_dark = np.zeros((len(dead_ids), r), bool)
        is_origin = dead_ids < r
        expect_dark[is_origin, dead_ids[is_origin]] = True
        np.testing.assert_array_equal(seen[~alive], expect_dark)
        per_rumor = seen[alive].mean(axis=0)
        for rr in range(r):
            if alive[rr]:              # origin of rumor rr is node rr
                assert per_rumor[rr] >= 0.99, (rr, per_rumor[rr])
            else:
                assert per_rumor[rr] == 0.0, (rr, per_rumor[rr])


# ---------------------------------------------------------------------------
# Reference-vs-Mosaic interpret equivalence.  ``interpret=True`` routes the
# fused entry points through the pure-JAX reference lowering (fast XLA — the
# driver/dry-run path); ``interpret="mosaic"`` forces the real Mosaic
# interpreter.  These tests pin them bitwise-equal on injected bits, so the
# kernel BODIES stay executed in CI and the reference can never drift.
# (Injected bits only: off-TPU the interpreter stubs the hardware PRNG.)

@pytest.mark.parametrize("fanout,sharing,drop_p,death",
                         [(1, 1, 0.0, 0.0),
                          # fault case rides the slow tier (tier-1 wall
                          # budget); the fault masks stay gated via
                          # test_kernel_fault_masks_match_numpy_model
                          pytest.param(2, 1, 0.3, 0.2,
                                       marks=pytest.mark.slow),
                          (1, 2, 0.0, 0.0)])
def test_reference_interpret_matches_mosaic_single_rumor(fanout, sharing,
                                                         drop_p, death):
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import fault_masks_node_packed
    n = 4096 * 8 - 37
    rng = np.random.default_rng(71 + fanout + sharing)
    rows = n_rows(n)
    table = jnp.asarray(np.asarray(node_pack(
        jnp.asarray(rng.random(n) < 0.05))))
    alive_tab, thresh = (None, 0)
    if drop_p or death:
        fault = FaultConfig(drop_prob=drop_p, node_death_rate=death, seed=3)
        alive_tab, thresh = fault_masks_node_packed(fault, n, 0)
    bits = _random_bits(rng, rows, fanout, sharing)
    kw = dict(inject_bits=bits, drop_threshold=thresh,
              alive_table=alive_tab, plane_sharing=sharing)
    ref = fused_pull_round(table, 0, 0, n, fanout, interpret=True, **kw)
    mos = fused_pull_round(table, 0, 0, n, fanout, interpret="mosaic", **kw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(mos))


@pytest.mark.parametrize("fanout,drop_p,death", [(1, 0.0, 0.0),
                                                 (2, 0.25, 0.15)])
def test_reference_interpret_matches_mosaic_multirumor(fanout, drop_p,
                                                       death):
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import fault_masks_word
    n = 128 * 16 - 29
    rng = np.random.default_rng(83 + fanout)
    rows = mr_rows(n)
    table = jnp.asarray(np.asarray(word_pack(
        jnp.asarray(rng.random((n, 16)) < 0.05))))
    alive_words, thresh = (None, 0)
    if drop_p or death:
        fault = FaultConfig(drop_prob=drop_p, node_death_rate=death, seed=5)
        alive_words, thresh = fault_masks_word(fault, n, 0)
    bits = _mr_bits(rng, rows, fanout)
    kw = dict(inject_bits=bits, drop_threshold=thresh,
              alive_words=alive_words)
    ref = fused_multirumor_pull_round(table, 0, 0, n, fanout,
                                      interpret=True, **kw)
    mos = fused_multirumor_pull_round(table, 0, 0, n, fanout,
                                      interpret="mosaic", **kw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(mos))


@pytest.mark.parametrize("fanout", [1, 2])
def test_reference_interpret_matches_mosaic_staged_big_path(fanout):
    """Both interpret impls of the STAGED path agree bitwise — and at
    fanout > 1 the mosaic route exercises the no-draw-0-alias donation
    rule (the fanout>1 fix) against the same operands."""
    from gossip_tpu.ops.pallas_round import _fused_mr_round_big
    n = 128 * 16 - 29
    rng = np.random.default_rng(97 + fanout)
    rows = mr_rows(n)
    table = jnp.asarray(np.asarray(word_pack(
        jnp.asarray(rng.random((n, 32)) < 0.04))))
    bits = _mr_bits(rng, rows, fanout)
    ref = _fused_mr_round_big(table, 0, 0, n, True, bits, fanout=fanout)
    mos = _fused_mr_round_big(table, 0, 0, n, "mosaic", bits,
                              fanout=fanout)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(mos))
    # the value kernel computes the same function on the same bits
    want = fused_multirumor_pull_round(table, 0, 0, n, fanout,
                                       interpret=True, inject_bits=bits)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(want))


def test_compiled_curve_fused_matches_stepwise():
    """The fixed-length curve scan is the SAME trajectory as stepping
    the kernel by hand (stubbed interpreter PRNG is deterministic),
    with the per-round coverage recorded — single-rumor and MR twins,
    fault masks included."""
    from gossip_tpu.config import FaultConfig
    from gossip_tpu.ops.pallas_round import (
        compiled_curve_fused, compiled_curve_fused_multirumor,
        fault_masks_node_packed, fused_cov_fn, fused_mr_cov_fn)
    n, rounds = 4096 * 8, 3
    fault = FaultConfig(node_death_rate=0.25, seed=3)
    scan, init = compiled_curve_fused(n, seed=0, max_rounds=rounds,
                                      interpret=True, fault=fault)
    final, covs = scan(init)
    assert covs.shape == (rounds,) and int(final.round) == rounds
    # stepwise twin
    alive_tab, thresh = fault_masks_node_packed(fault, n, 0)
    tab = init_fused_state(n, 0).table
    cov = fused_cov_fn(n, fault, 0)
    for t in range(rounds):
        tab = fused_pull_round(tab, 0, t, n, 1, interpret=True,
                               drop_threshold=thresh, alive_table=alive_tab)
        assert float(covs[t]) == float(cov(tab)), t
    np.testing.assert_array_equal(np.asarray(final.table), np.asarray(tab))

    n_mr, r = 128 * 16, 8
    scan_mr, init_mr = compiled_curve_fused_multirumor(
        n_mr, r, seed=0, max_rounds=rounds, interpret=True)
    final_mr, covs_mr = scan_mr(init_mr)
    assert covs_mr.shape == (rounds,)
    tab = init_multirumor_state(n_mr, r, 0).table
    cov_mr = fused_mr_cov_fn(n_mr, r)
    for t in range(rounds):
        tab = fused_multirumor_pull_round(tab, 0, t, n_mr, 1,
                                          interpret=True)
        assert float(covs_mr[t]) == float(cov_mr(tab)), t
    np.testing.assert_array_equal(np.asarray(final_mr.table),
                                  np.asarray(tab))
