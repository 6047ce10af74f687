"""Paged KV-cache subsystem tests.

Three layers, matching the subsystem's structure:
  * kernel: ``kernels.paged_attention`` (Pallas, interpret mode) and its
    pure-jnp reference vs the dense ``models.layers._sdpa`` oracle,
    including GQA groups and a partially-filled last page;
  * allocator: ``serving.BlockPool`` invariants under random staggered
    admit/grow/free interleavings (hypothesis when installed, a seeded
    sweep otherwise — same fallback idiom as tests/progs);
  * scheduler: the paged engine's greedy tokens are identical to the
    end-aligned engine's for requests that fit both, and it serves
    requests the end-aligned engine must reject at submit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.config import ParallelConfig
from repro.core import costmodel
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention, paged_attention_pallas
from repro.launch.scheduler import Request, Scheduler
from repro.launch.train import reduced
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving import BlockPool, PoolExhausted


def tiny(arch="llama3.2-3b", **kw):
    return reduced(configs.get(arch)).replace(
        dtype="float32", param_dtype="float32", vocab=64, **kw)


@pytest.fixture(scope="module")
def llama():
    cfg = tiny()
    return cfg, T.init(jax.random.PRNGKey(0), cfg)


PCFG = ParallelConfig(remat="none", fsdp_params=False)


# ---------------------------------------------------------------------------
# Kernel oracle: page view ≡ dense attention over the gathered sequence
# ---------------------------------------------------------------------------
def _paged_case(seed, b, hkv, rep, hd, n_blocks, blk, pages):
    """Random arena + per-request chains with garbage in unused blocks and
    beyond each row's valid length (masking must hide both), plus -1 tail
    table entries.  Lengths exercise the partially-filled last page."""
    rng = np.random.RandomState(seed)
    q = rng.randn(b, hkv, rep, hd).astype(np.float32)
    k = rng.randn(n_blocks, hkv, blk, hd).astype(np.float32)
    v = rng.randn(n_blocks, hkv, blk, hd).astype(np.float32)
    perm = rng.permutation(n_blocks)
    tables = np.full((b, pages), -1, np.int32)
    lengths = np.zeros((b,), np.int32)
    used = 0
    for row in range(b):
        # row 0 fills every page exactly; later rows end mid-page
        lengths[row] = pages * blk if row == 0 else rng.randint(1, pages * blk)
        chain = -(-int(lengths[row]) // blk)
        tables[row, :chain] = perm[used:used + chain]
        used += chain
    assert used <= n_blocks
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(lengths))


def _dense_oracle(q, k, v, tables, lengths):
    """Gather each chain into a dense (B, L, Hkv, hd) cache and run the
    model's own ``_sdpa`` with the valid-length mask."""
    b, hkv, _, hd = q.shape
    blk = k.shape[2]
    lmax = tables.shape[1] * blk
    kd = np.zeros((b, lmax, hkv, hd), np.float32)
    vd = np.zeros_like(kd)
    for row in range(b):
        for j, t in enumerate(np.asarray(tables[row])):
            if t >= 0:
                kd[row, j * blk:(j + 1) * blk] = np.asarray(k)[t].swapaxes(0, 1)
                vd[row, j * blk:(j + 1) * blk] = np.asarray(v)[t].swapaxes(0, 1)
    out = L._sdpa(q[:, None], jnp.asarray(kd), jnp.asarray(vd), causal=False,
                  window=None, q_offset=0, kv_len_valid=lengths)
    return out[:, 0]


@pytest.mark.parametrize("rep", [1, 4])           # MHA and a 4-wide GQA group
def test_paged_ref_matches_dense_sdpa(rep):
    case = _paged_case(0, b=3, hkv=2, rep=rep, hd=16, n_blocks=12, blk=4,
                       pages=3)
    got = ref.paged_attention(*case)
    want = _dense_oracle(*case)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("rep", [1, 4])
def test_paged_pallas_matches_ref(rep):
    case = _paged_case(1, b=2, hkv=2, rep=rep, hd=16, n_blocks=10, blk=4,
                       pages=4)
    got = paged_attention_pallas(*case, interpret=True)
    want = ref.paged_attention(*case)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # the auto-dispatch entry must agree too (ref backend off-TPU)
    auto = paged_attention(*case)
    np.testing.assert_allclose(auto, want, atol=1e-6, rtol=1e-6)


def test_paged_pallas_dead_rows_are_finite():
    """A row whose table is all -1 (parked/free slot) must produce finite
    output (the safe-divide path), not NaN that could poison downstream."""
    q, k, v, tables, lengths = _paged_case(2, b=2, hkv=2, rep=2, hd=8,
                                           n_blocks=6, blk=4, pages=2)
    tables = tables.at[1].set(-1)
    out = paged_attention_pallas(q, k, v, tables, lengths, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    out_ref = ref.paged_attention(q, k, v, tables, lengths)
    np.testing.assert_allclose(out[0], out_ref[0], atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# BlockPool allocator invariants
# ---------------------------------------------------------------------------
def _check_invariants(pool: BlockPool):
    live = [blkid for chain in pool._pages.values() for blkid in chain]
    assert len(live) == len(set(live)), "a block is aliased by two chains"
    assert sorted(live + pool._free) == list(range(pool.n_blocks)), \
        "free list + live chains must partition the pool"
    for rid, chain in pool._pages.items():
        assert len(chain) <= pool._reserved[rid]
    assert pool.reserved_blocks <= pool.n_blocks


def _drive_pool(ops, n_blocks=16, block=4):
    """Replay an op sequence against a pool, checking invariants after every
    step.  ops: list of (kind, value) with kind in admit/grow/free."""
    pool = BlockPool(n_blocks, block)
    live = {}                                    # rid -> (tokens, total)
    next_rid = 0
    for kind, value in ops:
        if kind == "admit":
            total = 1 + value % (n_blocks * block)
            if pool.can_admit(total):
                pool.admit(next_rid, total)
                live[next_rid] = [0, total]
                next_rid += 1
            else:
                with pytest.raises(PoolExhausted):
                    pool.admit(next_rid, total)
                next_rid += 1                    # rid burned, not admitted
        elif kind == "grow" and live:
            rid = sorted(live)[value % len(live)]
            cur, total = live[rid]
            tokens = min(cur + 1 + value % block, total)
            chain = pool.ensure(rid, tokens)
            assert len(chain) == pool.blocks_needed(tokens) or tokens == 0
            live[rid][0] = tokens
            # the fixed-width table row mirrors the chain, -1 tail
            row = pool.table(rid, pool.n_blocks)
            assert list(row[:len(chain)]) == chain
            assert all(row[len(chain):] == -1)
        elif kind == "free" and live:
            rid = sorted(live)[value % len(live)]
            pool.free(rid)
            del live[rid]
        _check_invariants(pool)
    for rid in sorted(live):
        pool.free(rid)
        _check_invariants(pool)
    assert pool.live_blocks == 0 and pool.free_blocks == pool.n_blocks


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.sampled_from(["admit", "grow", "free"]),
                              st.integers(0, 10 ** 6)), max_size=60))
    def test_block_pool_random_interleavings(ops):
        """Staggered alloc/free never aliases live pages; the free list
        conserves blocks; reservations never oversubscribe."""
        _drive_pool(ops)
except ImportError:                              # seeded fallback sweep
    def test_block_pool_random_interleavings():
        rng = np.random.RandomState(0)
        for _ in range(50):
            ops = [(["admit", "grow", "free"][rng.randint(3)],
                    int(rng.randint(10 ** 6)))
                   for _ in range(rng.randint(1, 60))]
            _drive_pool(ops)


def test_block_pool_units():
    pool = BlockPool(4, 8)
    assert pool.blocks_needed(1) == 1 and pool.blocks_needed(8) == 1
    assert pool.blocks_needed(9) == 2
    pool.admit(0, 20)                            # reserves 3 of 4
    assert not pool.can_admit(9) and pool.can_admit(8)
    with pytest.raises(PoolExhausted):
        pool.admit(1, 9)
    pool.ensure(0, 5)
    with pytest.raises(PoolExhausted):           # beyond the reservation
        pool.ensure(0, 25)
    rep = pool.report()
    assert rep["live_blocks"] == 1 and rep["reserved_blocks"] == 3
    assert rep["occupancy"] == 0.25
    assert rep["internal_frag"] == pytest.approx(1 - 5 / 8)
    pool.free(0)
    assert pool.report()["occupancy"] == 0.0
    assert pool.report()["peak_occupancy"] == 0.25
    with pytest.raises(ValueError):
        BlockPool(0, 8)


# ---------------------------------------------------------------------------
# Scheduler: paged engine vs the end-aligned oracle
# ---------------------------------------------------------------------------
def test_paged_tokens_identical_to_end_aligned(llama):
    """For requests that fit both engines, paged greedy output is
    token-identical to the end-aligned engine's — chunked prefill through
    pages computes the same sequence the fused end-aligned prefill does
    (heterogeneous staggered mix incl. an empty prompt; chunk chosen to
    leave a partial final slice, block to leave a partial last page)."""
    cfg, params = llama
    rng = np.random.RandomState(7)
    spec = [(5, 3, 0), (2, 4, 0), (7, 2, 1), (0, 3, 3)]
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (lp,)).astype(np.int32),
                    gen=gen, arrival=arr)
            for i, (lp, gen, arr) in enumerate(spec)]

    ea = Scheduler(cfg, PCFG, params, slots=2, max_len=16, bucket=8)
    out_ea = ea.run(reqs)
    pg = Scheduler(cfg, PCFG, params, slots=2, max_len=16, paged=True,
                   block=4, chunk=3)
    out_pg = pg.run(reqs)
    for i, (lp, gen, _) in enumerate(spec):
        assert out_pg["completions"][i].tokens == out_ea["completions"][i].tokens, i
        assert len(out_pg["completions"][i].tokens) == gen
    # eviction drained the pool; the run used it
    assert out_pg["pool"]["occupancy"] == 0.0
    assert out_pg["pool"]["peak_occupancy"] > 0.0


def test_paged_final_chunk_pad_overflow_does_not_corrupt(llama):
    """Regression: the final right-padded chunk's pad positions can run past
    the block-table width; an unguarded gather CLAMPS to the last (live)
    table entry and scatters pad K/V over real prompt tokens.  chunk=9 /
    block=4 / prompt=13 / max_len=16 puts pad tpos 16 and 17 one page past
    the 4-wide table."""
    cfg, params = llama
    rng = np.random.RandomState(5)
    req = Request(rid=0, prompt=rng.randint(0, cfg.vocab, (13,)).astype(np.int32),
                  gen=3)
    ea = Scheduler(cfg, PCFG, params, slots=1, max_len=16).run([req])
    pg = Scheduler(cfg, PCFG, params, slots=1, max_len=16, paged=True,
                   block=4, chunk=9).run([req])
    assert pg["completions"][0].tokens == ea["completions"][0].tokens


def test_paged_serves_beyond_end_aligned_capacity(llama):
    """The acceptance scenario: same total cache memory (pool_blocks*block
    == slots*max_len tokens), but prompt+gen exceeds the per-slot row — the
    end-aligned engine must reject at submit; the paged engine serves it
    and matches an end-aligned oracle given a big-enough slot."""
    cfg, params = llama
    rng = np.random.RandomState(11)
    big = Request(rid=0, prompt=rng.randint(0, cfg.vocab, (10,)).astype(np.int32),
                  gen=6)                          # 16 tokens > max_len 8

    ea = Scheduler(cfg, PCFG, params, slots=2, max_len=8)
    with pytest.raises(ValueError, match="end-aligned slot capacity"):
        ea.submit(big)

    pg = Scheduler(cfg, PCFG, params, slots=2, max_len=16, paged=True,
                   block=4, pool_blocks=4, chunk=4)   # 4*4 == 2*8 tokens
    out = pg.run([big])
    oracle = Scheduler(cfg, PCFG, params, slots=1, max_len=20)
    ref_toks = oracle.run([Request(rid=0, prompt=big.prompt, gen=6)])
    assert out["completions"][0].tokens == ref_toks["completions"][0].tokens
    assert out["pool"]["peak_occupancy"] == 1.0   # it genuinely needed the pool


def test_submit_validates_with_named_limits(llama):
    """Satellite: length validation happens at submit() time with an error
    naming the limit — pool-capacity-based in paged mode."""
    cfg, params = llama
    ea = Scheduler(cfg, PCFG, params, slots=1, max_len=8)
    with pytest.raises(ValueError, match=r"max_len=8"):
        ea.submit(Request(rid=0, prompt=np.zeros(6, np.int32), gen=5))
    with pytest.raises(ValueError, match="gen >= 1"):
        ea.submit(Request(rid=1, prompt=np.zeros(2, np.int32), gen=0))

    pg = Scheduler(cfg, PCFG, params, slots=1, max_len=64, paged=True,
                   block=4, pool_blocks=8, chunk=4)
    with pytest.raises(ValueError, match=r"pool capacity is 8 blocks"):
        pg.submit(Request(rid=2, prompt=np.zeros(40, np.int32), gen=8))
    with pytest.raises(ValueError, match="block-table width"):
        pg.submit(Request(rid=3, prompt=np.zeros(60, np.int32), gen=8))
    # a fitting request passes and runs from the queue
    pg.submit(Request(rid=4, prompt=np.zeros(3, np.int32), gen=2))
    out = pg.run()
    assert list(out["completions"]) == [4]


def test_paged_requires_pure_attention():
    cfg = tiny("xlstm-1.3b").replace(block_pattern=("mlstm",), n_layers=1)
    params = T.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="pure-attention"):
        Scheduler(cfg, PCFG, params, slots=1, max_len=8, paged=True)
    with pytest.raises(NotImplementedError):
        T.init_paged_cache(cfg, 4, 4)


# ---------------------------------------------------------------------------
# Cost model: page-gather tax and the chunked-prefill stall tradeoff
# ---------------------------------------------------------------------------
def test_paged_decode_cost_converges_to_dense():
    n, b, kvb, kvt = 3e9, 32, 2 ** 20, 2 ** 12
    dense = costmodel.decode_step_cost(n, b, kvb)
    prev = None
    for blk in (8, 64, 512, 2 ** 20):
        paged = costmodel.paged_decode_step_cost(n, b, kvb, block=blk,
                                                 kv_token_bytes=kvt)
        assert paged["total_s"] >= dense["total_s"] - 1e-12
        if prev is not None:
            assert paged["total_s"] <= prev + 1e-12   # bigger pages, less tax
        prev = paged["total_s"]
    assert paged["pages_per_seq"] == 1
    assert paged["total_s"] == pytest.approx(dense["total_s"], rel=1e-3)


def test_chunked_prefill_stall_tradeoff():
    n, prompt, kvt = 3e9, 4096, 2 ** 12
    fused = costmodel.prefill_cost(n, prompt)
    one = costmodel.chunked_prefill_cost(n, prompt, prompt)
    assert one["n_chunks"] == 1
    assert one["total_s"] == pytest.approx(fused["total_s"], rel=1e-6)
    prev_total, prev_stall = one["total_s"], one["stall_s"]
    for chunk in (1024, 256, 64):
        c = costmodel.chunked_prefill_cost(n, prompt, chunk,
                                           kv_token_bytes=kvt)
        assert c["total_s"] >= prev_total - 1e-12     # chunking costs total…
        assert c["stall_s"] <= prev_stall + 1e-12     # …but bounds the stall
        prev_total, prev_stall = c["total_s"], c["stall_s"]
