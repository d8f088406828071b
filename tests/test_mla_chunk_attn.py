"""The chunk's latent attention kernel (``mla_paged_chunk_attn``) against
its lax twin, in interpret mode on the CPU: one parametrised test a
property, at the head widths of the two cells that run it (192 / 128 with
a key of 128 + 64, xing4; 256 / 256 with a key of 192 + 64, glm52).

Bounds: float32 operands agree to ``rtol=2e-4, atol=2e-5`` (the decode
kernel's bound against ``reference_mla_paged_decode_attention`` in
``tests/test_xing4.py``: the same products summed in another order); bf16
operands to 0.03 on outputs of order 1 (``chip_smoke.py``'s bound)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.ops import mla_attention as mla

TOL = dict(rtol=2e-4, atol=2e-5)
WIDTHS = [(128, 64, 128), (192, 64, 256)]  # d_nope, d_rope, d_v
H, T, PS, PPS, C = 2, 32, 16, 8, 128


@pytest.fixture(autouse=True, params=[16, 48], ids=["tile16", "tile48"])
def small_blocks(request, monkeypatch):
    """Two query blocks a chunk, so that a block skips the tiles past it;
    a tile of one page, and one of three (the table's eight pages are no
    multiple of it: the last tile's third page is past the table)."""
    monkeypatch.setattr(mla, "_CHUNK_Q_ROWS", 16)
    monkeypatch.setattr(mla, "_CHUNK_K_ROWS", request.param)


def _inputs(widths, slots, *, dtype=jnp.float32, seed=0):
    dn, dr, dv = widths
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    pages = slots * PPS + 3
    kr = jnp.pad(f(pages, PS, dr),
                 ((0, 0), (0, 0), (0, mla.lane_pad(dr) - dr)))
    table = rng.permutation(pages)[: slots * PPS].reshape(slots, PPS)
    return dict(
        q_nope=f(slots, T, H, dn), q_rope=f(slots, T, H, dr),
        ckv_pool=f(pages, PS, C), kr_pool=kr,
        block_table=jnp.asarray(table, jnp.int32),
        w_ukv=0.1 * f(C, H, dn + dv)), (dn + dr) ** -0.5


def _both(x, lengths, scale, select=None, **over):
    x = {**x, **over}
    args = (x["q_nope"], x["q_rope"], x["ckv_pool"], x["kr_pool"],
            jnp.asarray(lengths, jnp.int32), x["block_table"], x["w_ukv"])
    want = mla.reference_mla_paged_prefill_attention(
        *args, scale=scale, tile=2 * PS, select=select)
    got = mla.mla_paged_prefill_attention(
        *args, scale=scale, select=select, interpret=True)
    return got, want


def _sees(select, lengths):
    """[B, T]: whether a row attends to any position at all."""
    pos = jnp.asarray(lengths)[:, None] + jnp.arange(T)[None, :]
    return jnp.any(
        select & (jnp.arange(PPS * PS)[None, None] <= pos[:, :, None]), -1)


@pytest.mark.parametrize("widths", WIDTHS)
def test_ragged_lengths_with_a_first_chunk(widths):
    """Participants at their own fills, one at 0: each stops at its own
    last visible tile and masks its own diagonal."""
    x, scale = _inputs(widths, 3)
    got, want = _both(x, [0, 48, 96], scale)
    assert got.shape == want.shape == (3, T, H, widths[2])
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("widths", WIDTHS)
@pytest.mark.parametrize("prefix", [5, 37])
def test_a_prefix_that_is_no_multiple_of_a_block(widths, prefix):
    """The chunk starts inside a page: neither the twin's tile (32) nor
    the kernel's key block (16) nor its query block (16) divides it."""
    x, scale = _inputs(widths, 2, seed=prefix)
    got, want = _both(x, [prefix, prefix + 20], scale)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("widths", WIDTHS)
def test_a_participant_of_padding_rows_stays_finite(widths):
    """A participant none of whose rows sees anything (the choice is empty
    for it, as for a seat of padding): finite output, and its neighbour's
    rows are the twin's."""
    x, scale = _inputs(widths, 2, seed=3)
    select = jnp.ones((2, T, PPS * PS), bool).at[1].set(False)
    got, want = _both(x, [40, 40], scale, select)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got[0], want[0], **TOL)


@pytest.mark.parametrize("widths", WIDTHS)
def test_the_choice_is_a_second_condition_on_visibility(widths):
    """Eight positions a row where a row sees that many, fewer in a first
    chunk's early rows; a key block in which a query block sees nothing
    (the second block of the first participant's chunk is never chosen)
    and a row that sees nothing before its last tile."""
    x, scale = _inputs(widths, 2, seed=4)
    lengths = [0, 70]
    rng = np.random.default_rng(5)
    pos = np.asarray(lengths)[:, None] + np.arange(T)[None, :]
    select = np.zeros((2, T, PPS * PS), bool)
    for b in range(2):
        for t in range(T):
            seen = np.arange(pos[b, t] + 1)
            select[b, t, rng.choice(seen, min(8, len(seen)), False)] = True
    select[0, :, PS:2 * PS] = False
    select[1, 3] = False
    select[1, 3, pos[1, 3]] = True  # only itself
    select = jnp.asarray(select)
    assert int(jnp.sum(select[0, 2])) < 8  # a row with fewer than its k
    got, want = _both(x, lengths, scale, select)
    sees = _sees(select, lengths)[:, :, None, None]
    np.testing.assert_allclose(
        jnp.where(sees, got, 0.0), jnp.where(sees, want, 0.0), **TOL)
    assert bool(jnp.all(jnp.isfinite(got)))


@pytest.mark.parametrize("widths", WIDTHS)
def test_table_entries_past_the_last_page_may_name_any_page(widths):
    """A slot's table past its last visible page is never read."""
    x, scale = _inputs(widths, 2, seed=6)
    lengths = [10, 50]  # last visible positions 41 and 81: pages 0-2, 0-5
    table = np.asarray(x["block_table"]).copy()
    table[0, 3:] = -1
    table[1, 6:] = 10**6
    got, _ = _both(x, lengths, scale, block_table=jnp.asarray(table))
    _, want = _both(x, lengths, scale)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("widths", WIDTHS)
@pytest.mark.parametrize("masked", [False, True])
def test_bf16_operands_within_the_chip_bound(widths, masked):
    """The cells' precision: bf16 pools, queries and weights, float32
    scores and statistics, ``p`` cast before the weighted sum."""
    x, scale = _inputs(widths, 2, dtype=jnp.bfloat16, seed=7)
    lengths = [21, 90]
    select = None
    if masked:
        select = jnp.asarray(np.random.default_rng(8).random(
            (2, T, PPS * PS)) < 0.5).at[:, :, 0].set(True)
    got, want = _both(x, lengths, scale, select)
    assert got.dtype == jnp.bfloat16
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) <= 0.03, float(err)


@pytest.mark.parametrize(
    "dn,dr,r,rope_at", [(128, 64, 128, 128), (192, 64, 128, 192),
                        (32, 16, 128, 32), (96, 64, 128, 128)])
def test_the_rotary_part_lies_where_the_key_has_room(dn, dr, r, rope_at):
    """Straight after the expanded key on a lane tile's edge or where the
    tile's rest holds it (192 + 64 is one 256-lane key), else in tiles of
    its own."""
    bq, _, at = mla.pick_mla_chunk_blocks(64, 256, dn, dr, r)
    assert (bq, at) == (16, rope_at)


@pytest.mark.parametrize("page,want,tile", [(256, 1024, 1024), (256, 96, 64),
                                            (16, 48, 48), (16, 40, 32)])
def test_a_tile_is_a_part_of_a_page_or_whole_pages(monkeypatch, page, want,
                                                   tile):
    monkeypatch.setattr(mla, "_CHUNK_K_ROWS", want)
    assert mla.pick_mla_chunk_blocks(64, page, 128, 64, 128)[1] == tile


def test_the_spans_say_which_latent_kernel_ran(monkeypatch):
    monkeypatch.setattr(mla, "_CHUNK_Q_ROWS", 512)
    monkeypatch.setattr(mla, "_CHUNK_K_ROWS", 1024)
    assert mla.latent_attention_tiling(1, 256, 128, 64) == {
        "attention_form": "latent_absorbed", "attention_rows": 256}
    assert mla.latent_attention_tiling(2048, 256, 128, 64) == {
        "attention_form": "latent_expanded_kernel", "attention_rows": 1024,
        "attention_query_rows": 512}
