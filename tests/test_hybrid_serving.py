"""A hybrid stack on the paged serving path: sliding-window rings beside
full-attention pages, a leading dense layer, and an expert layer that holds
a share of the experts (K-EXAONE's block at a tiny size).

  * config: ``ModelConfig`` built from the benchmark's JSON file;
  * expert share: the shares of all the experts, with the shared expert
    counted once, add up to the uncut layer;
  * kernel: the paged decode kernel's per-row first key position, in the
    jnp reference and the Pallas kernel in interpret mode;
  * serving: chunked prefill then paged decode through the scheduler's own
    step programs and page pool, against the plain reference's full forward
    pass on logits, and ``Scheduler.run`` end to end with its tick log.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, MoEConfig, ParallelConfig
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.launch.scheduler import Request, Scheduler
from repro.models import moe as M
from repro.models import transformer as T
from repro.parallel import steps as S
from repro.serving import telemetry

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
LLLG = ["sliding_attention"] * 3 + ["full_attention"]
# two LLLG periods, a dense layer 0; window 8 at pages of 4 and chunks of 8,
# so every ring (5 pages) wraps; 16 experts of which 4 are held, top 4;
# float32 activations over the weights the reference draws (bfloat16)
TINY = {"name": "tiny-exaone", "family": "moe", "n_layers": 8, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
        "vocab": 256, "block_pattern": ["attn_moe"], "first_k_dense": 1,
        "rope_fraction": 1.0, "rope_theta": 10000.0, "window": 8,
        "layer_types": LLLG * 2, "full_rope": False, "qk_norm": True,
        "post_norm": True, "norm": "rmsnorm", "act": "swiglu",
        "norm_eps": 1e-5,
        "moe": {"n_experts": 16, "top_k": 4, "d_ff_expert": 32,
                "n_shared_experts": 1, "scoring": "sigmoid",
                "routed_scale": 2.5, "n_held": 4},
        "tie_embeddings": False, "dtype": "float32",
        "param_dtype": "bfloat16"}
SEED = 5
BLOCK, CHUNK, SLOTS, MAX_LEN = 4, 8, 2, 64
PCFG = ParallelConfig(remat="none", fsdp_params=False)
# program (float32 activations, float32 K/V) against the float32 reference
# at `highest`: the two differ only in the order of float32 sums (online
# softmax over pages, chunks, grouped expert matmuls), a few 1e-6 on logits
# of order 1; the same comparison in bfloat16 activations and K/V misses by
# 1e-2 and more, so it sees a lost precision step
LOGIT_TOL = 1e-4


def _reference():
    sys.path.insert(0, str(CHIP))
    from chipbench import spec
    return spec.load_module(CHIP / "references" / "exaone_moe.py")


def _cfg(**kw):
    return ModelConfig(**dict(TINY, **kw))


def test_model_config_from_the_benchmark_json():
    """The benchmark builds the model as ``ModelConfig(**config["model"])``:
    nested groups arrive as dicts and tuples as lists."""
    m = json.loads((CHIP / "configs" / "k-exaone-236b-8l.json").read_text())
    cfg = ModelConfig(**m["model"])
    assert isinstance(cfg.moe, MoEConfig)
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.top_k) == (128, 8, 8)
    assert cfg.layer_types == tuple(LLLG * 2) and hash(cfg)
    # layer 0 is dense, so the scan's body is the whole stack of 8 layers
    assert cfg.period == ("attn",) + ("attn_moe",) * 7 and cfg.n_periods == 1
    assert [c.window for c in cfg.period_cfgs] == [128, 128, 128, None] * 2
    # no rotary on the full layers of the hybrid
    assert [c.rope_fraction for c in cfg.period_cfgs] == [1.0, 1.0, 1.0, 0.0] * 2
    assert T.supports_paged(cfg)
    # the file's catalog keys hold the configuration as it is run
    assert m["num_hidden_layers"] == cfg.n_layers
    assert m["num_experts"] == cfg.moe.held
    assert m["layer_types"] == list(cfg.layer_types)
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig(**dict(m["model"], layer_types=LLLG))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their outputs, with the shared
    expert (which every chip computes alike) counted once, sum to the layer
    that holds all sixteen; each share draws exactly its experts' weights."""
    whole = _cfg(moe=dict(TINY["moe"], n_held=None))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 64), jnp.float32)
    key = jax.random.PRNGKey(2)
    p_all = M.moe_init(key, whole)
    want, _, held = M.moe_ffn(p_all, x, whole, None)
    np.testing.assert_array_equal(held, 4)
    shared = M._shared_ffn(x.reshape(-1, 64), p_all["shared"], whole, None)
    total = -3 * shared.reshape(x.shape)
    counts = 0
    for s in range(4):
        cfg = _cfg(moe=dict(TINY["moe"], n_held=4, first_held=4 * s))
        p = M.moe_init(key, cfg)
        for w in ("w_gate", "w_up", "w_down"):
            np.testing.assert_array_equal(p[w], p_all[w][4 * s:4 * s + 4])
        got, _, held = M.moe_ffn(p, x, cfg, None)
        total = total + got
        counts = counts + held
    np.testing.assert_allclose(total, want, rtol=0, atol=2e-5)
    # held assignments: every token has top_k of them over the four shares
    np.testing.assert_array_equal(counts, 4)


def _kernel_case(seed=3, b=3, hkv=2, rep=2, hd=32, blk=16, pages=40):
    rng = np.random.RandomState(seed)
    n = b * pages + 2
    q = jnp.asarray(rng.randn(b, hkv, rep, hd), jnp.float32)
    k = jnp.asarray(rng.randn(n, hkv, blk, hd), jnp.float32)
    v = jnp.asarray(rng.randn(n, hkv, blk, hd), jnp.float32)
    tables = np.full((b, pages), -1, np.int32)
    lengths = np.array([pages * blk, 37, 600], np.int32)[:b]
    perm = rng.permutation(n)
    used = 0
    for row in range(b):
        c = -(-int(lengths[row]) // blk)
        tables[row, :c] = perm[used:used + c]
        used += c
    return q, k, v, jnp.asarray(tables), jnp.asarray(lengths)


@pytest.mark.parametrize("kernel", ["ref", "pallas_interpret"])
def test_paged_attention_first_key_position(kernel):
    """A start of 0 is today's attention; a start > 0 masks the keys below
    it (a sliding window's), where it falls inside a page, and where the
    walk (32 pages a group at 16-token pages) skips its first group."""
    q, k, v, tables, lengths = _kernel_case()
    fn = ref.paged_attention if kernel == "ref" else (
        lambda *a: paged_attention_pallas(*a, interpret=True))
    base = fn(q, k, v, tables, lengths)
    zero = fn(q, k, v, tables, lengths, jnp.zeros_like(lengths))
    np.testing.assert_array_equal(np.asarray(zero), np.asarray(base))
    starts = jnp.asarray([520, 0, 599], jnp.int32)   # mid-page, none, last
    got = fn(q, k, v, tables, lengths, starts)
    # the oracle: the dense view with the keys below the start masked
    kk, vv = ref.gather_pages(k, tables), ref.gather_pages(v, tables)
    kpos = jnp.arange(kk.shape[1])
    s = jnp.einsum("bgrd,bkgd->bgrk", q / np.sqrt(q.shape[-1]), kk)
    mask = (kpos[None] < lengths[:, None]) & (kpos[None] >= starts[:, None])
    p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -1e30), axis=-1)
    want = jnp.einsum("bgrk,bkgd->bgrd", p, vv)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _serve_steps(cfg, params, prompts, gens):
    """Chunked prefill, then paged decode, through the scheduler's own
    prefill program, page pool and cache layout and the logits-returning
    paged decode step: per request, the logits of every served position."""
    sched = Scheduler(cfg, PCFG, params, slots=SLOTS, max_len=MAX_LEN,
                      paged=True, block=BLOCK, chunk=CHUNK,
                      pool_blocks=SLOTS * MAX_LEN // BLOCK)
    decode = jax.jit(S.make_decode_step(cfg, PCFG, None, return_logits=True,
                                        paged=True))
    pool, cache, n_pages = sched.pool, sched.cache, sched.n_pages
    out = [[] for _ in prompts]
    for slot, p in enumerate(prompts):
        pool.admit(slot, len(p) + gens)
        lo = 0
        while lo < len(p):
            ln = min(CHUNK, len(p) - lo)
            pool.ensure(slot, lo + ln)
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :ln] = p[lo:lo + ln]
            lg, cache = sched._chunk_prefill(
                params, jnp.asarray(toks), cache, jnp.int32(lo),
                jnp.asarray(pool.table(slot, n_pages)[None]), jnp.int32(ln),
                jnp.int32(slot))
            lo += ln
        out[slot].append(np.asarray(lg[0]))
    pos = np.array([len(p) for p in prompts], np.int32)
    tables = np.full((SLOTS, n_pages), -1, np.int32)
    for _ in range(gens - 1):
        for slot in range(SLOTS):
            pool.ensure(slot, int(pos[slot]) + 1)
            tables[slot] = pool.table(slot, n_pages)
        tok = np.array([o[-1].argmax() for o in out], np.int32)
        lg, cache = decode(params, jnp.asarray(tok), cache, jnp.asarray(pos),
                           jnp.asarray(tables))
        for slot in range(SLOTS):
            out[slot].append(np.asarray(lg[slot]))
        pos += 1
    return [np.stack(o) for o in out]


def test_paged_hybrid_matches_the_reference_on_logits():
    """Prompts of 29 and 13 tokens (4 and 2 chunks), 20 tokens each: every
    ring wraps in prefill and again in decode, in both slots."""
    ref_mod = _reference()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (29, 13)]
    gens = 20
    errs = {}
    for dtype in ("float32", "bfloat16"):
        cfg = _cfg(dtype=dtype)
        params = T.init(jax.random.PRNGKey(SEED), cfg)
        got = _serve_steps(cfg, params, prompts, gens)
        seqs = [(p, g.argmax(axis=1).astype(np.int32))
                for p, g in zip(prompts, got)]
        want = ref_mod.logits(TINY, SEED, seqs)
        errs[dtype] = max(float(np.abs(g - np.asarray(w[:gens])).max())
                          for g, w in zip(got, want))
    assert errs["float32"] < LOGIT_TOL, errs
    assert errs["bfloat16"] > 10 * LOGIT_TOL, errs


def test_scheduler_serves_the_hybrid_as_the_reference(monkeypatch):
    """``Scheduler.run`` end to end: staggered requests reuse both slots'
    rings; every served token is the reference's best (gap 0 up to float32
    rounding), and the tick log counts the held experts' rows."""
    ref_mod = _reference()
    cfg = _cfg()
    params = T.init(jax.random.PRNGKey(SEED), cfg)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n).astype(np.int32),
                    gen=g, arrival=a)
            for i, (n, g, a) in enumerate([(29, 17, 0), (13, 25, 0),
                                           (40, 9, 3), (5, 30, 8)])]
    sched = Scheduler(cfg, PCFG, params, slots=SLOTS, max_len=MAX_LEN,
                      paged=True, block=BLOCK, chunk=CHUNK, pool_blocks=40)
    log = []
    monkeypatch.setattr(telemetry, "LOG", log)
    out = sched.run(reqs)
    seqs = [(np.asarray(r.prompt),
             np.asarray(out["completions"][r.rid].tokens, np.int32))
            for r in reqs]
    for (_, served), lg in zip(seqs, ref_mod.logits(TINY, SEED, seqs)):
        padded = np.zeros(lg.shape[0], np.int32)
        padded[:len(served)] = served
        gaps = np.asarray(ref_mod.gap_of(lg, jnp.asarray(padded)))
        assert gaps[:len(served)].max() < LOGIT_TOL
    rows = [r.expert_rows for r in log]
    # at most every decode row on a held expert for each pick in 7 layers
    assert sum(rows) > 0 and max(rows) <= SLOTS * 7 * 4


def test_expert_rows_count_every_assignment_when_all_are_held(monkeypatch):
    """With every expert held, each decoding row puts top_k assignments on
    held experts in each of the 7 expert layers, and parked rows none."""
    cfg = _cfg(moe=dict(TINY["moe"], n_held=None))
    params = T.init(jax.random.PRNGKey(SEED), cfg)
    rng = np.random.default_rng(2)
    reqs = [Request(rid=0, prompt=rng.integers(0, 256, 11).astype(np.int32),
                    gen=6),
            Request(rid=1, prompt=rng.integers(0, 256, 3).astype(np.int32),
                    gen=12, arrival=2)]
    sched = Scheduler(cfg, PCFG, params, slots=3, max_len=MAX_LEN,
                      paged=True, block=BLOCK, chunk=CHUNK, pool_blocks=48)
    log = []
    monkeypatch.setattr(telemetry, "LOG", log)
    steps = {}

    def on_token(rid, tok):
        steps.setdefault(rid, 0)
        steps[rid] += 1

    sched.run(reqs, on_token=on_token)
    # a request's first token comes from prefill, the rest from decode steps
    decoded = sum(n - 1 for n in steps.values())
    assert sum(r.expert_rows for r in log) == decoded * 7 * 4


def test_rings_do_not_grow_with_the_context():
    """The windowed layers' rings hold the window plus a chunk in pages
    whatever the table width; the pool counts full-layer pages only."""
    cfg = _cfg()
    params = T.init(jax.random.PRNGKey(SEED), cfg)
    shapes = []
    for max_len in (64, 256):
        s = Scheduler(cfg, PCFG, params, slots=SLOTS, max_len=max_len,
                      paged=True, block=BLOCK, chunk=CHUNK, pool_blocks=50)
        assert s.ring_pages == -(-(8 + CHUNK) // BLOCK) + 1 == 5
        assert s.pool.n_blocks == 50
        shapes.append([c["attn"][0].shape for c in s.cache])
    ring = (1, SLOTS, 5, 2, BLOCK, 16)
    full = (1, 50, 2, BLOCK, 16)
    assert shapes[0] == shapes[1] == [ring] * 3 + [full] + [ring] * 3 + [full]


@pytest.mark.parametrize("scoring,scale", [("softmax", 1.0), ("sigmoid", 2.5)])
def test_router_weights_sum_to_the_routed_scale(scoring, scale):
    """The picked experts' scores, divided by their sum, times
    ``routed_scale``: each token's weights sum to the scale, and the picks
    are the top_k by score (sigmoid keeps softmax's order)."""
    e = MoEConfig(n_experts=16, top_k=4, d_ff_expert=32, scoring=scoring,
                  routed_scale=scale)
    x = jax.random.normal(jax.random.PRNGKey(3), (7, 64), jnp.float32)
    w_r = jax.random.normal(jax.random.PRNGKey(4), (64, 16), jnp.float32)
    top_i, weights, probs = M._route(x, w_r, e)
    np.testing.assert_allclose(weights.sum(-1), scale, rtol=1e-6)
    want = np.argsort(-np.asarray(x @ w_r), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(top_i, -1), np.sort(want, -1))
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)


def test_held_experts_must_lie_under_the_router():
    """A share that reaches past the router's experts is refused."""
    MoEConfig(n_experts=16, top_k=4, d_ff_expert=32, n_held=4, first_held=12)
    with pytest.raises(ValueError, match="held experts"):
        MoEConfig(n_experts=16, top_k=4, d_ff_expert=32, n_held=4,
                  first_held=13)


def test_ring_is_never_wider_than_the_table():
    """Where the whole context fits in fewer pages than a ring, the ring is
    the table's width: logical page j is ring page j, as in a full layer."""
    cfg = _cfg()
    params = T.init(jax.random.PRNGKey(SEED), cfg)
    s = Scheduler(cfg, PCFG, params, slots=SLOTS, max_len=12, paged=True,
                  block=BLOCK, chunk=CHUNK, pool_blocks=8)
    assert s.n_pages == 3 and s.ring_pages == 3
    assert s.cache[0]["attn"][0].shape == (1, SLOTS, 3, 2, BLOCK, 16)
