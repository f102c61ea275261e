"""repro.serve — paged KV arena, flash-decode kernel, continuous batching.

Layers, bottom-up: arena plan arithmetic and the page allocator; the
flash-decode kernel against its op-for-op blockwise mirror (lockstep
tolerance) and its own determinism (bitwise); the split/combine LSE
identity; the paged engine against the contiguous ``decode_step`` oracle
(allclose); the lowered-HLO collective pins the dry-run asserts (0
collectives at R=1 in-process, ``2·n_layers`` at R=2 in a subprocess);
the gathered-serving decoder-only guard; and the continuous-vs-static
scheduler, both on a step-exact fake engine (throughput ratio ≥ 2×) and
end-to-end on the real one (identical logits under both policies).
"""

import numpy as np
import pytest

from conftest import run_distributed


def _jnp():
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# KV arena plan + allocator
# ---------------------------------------------------------------------------


def _plan(**kw):
    from repro.configs import reduced_config
    from repro.serve import plan_kv_arena

    cfg = reduced_config(kw.pop("arch", "llama3.2-1b"))
    kw.setdefault("page_bytes", 4096)
    return cfg, plan_kv_arena(cfg, **kw)


def test_kv_plan_arithmetic():
    import jax.numpy as jnp

    cfg, plan = _plan(page_tokens=8, max_seqs=4, max_seq_len=64)
    hkv, d = cfg.attn.num_kv_heads, cfg.attn.head_dim
    assert plan.payload_elems == 2 * hkv * 8 * d          # K and V halves
    assert plan.v_offset == hkv * 8 * d and plan.k_offset == 0
    assert plan.max_blocks == -(-64 // 8)
    assert plan.n_kv_pages == 4 * plan.max_blocks * plan.n_layers
    # equal payloads -> one stride; offsets are exactly id * stride
    assert plan.page_stride == plan.layout.segments[0].padded
    for pid in (0, 1, plan.n_kv_pages - 1):
        assert plan.page_offset(pid) == pid * plan.page_stride
    assert plan.total_elems == plan.n_kv_pages * plan.page_stride
    assert plan.total_bytes == plan.n_arena_pages * 4096
    assert 0.0 <= plan.padding_fraction < 1.0
    assert plan.zeros().shape == (plan.total_elems,)
    assert plan.zeros().dtype == jnp.bfloat16
    d_ = plan.describe()
    assert d_["n_kv_pages"] == plan.n_kv_pages
    assert d_["total_bytes"] == plan.total_bytes


def test_kv_plan_pads_blocks_to_model_axis():
    from types import SimpleNamespace

    import jax
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    _, p1 = _plan(page_tokens=8, max_seqs=2, max_seq_len=24, mesh=mesh)
    assert p1.model_parallel == 1 and p1.max_blocks == 3
    # a 4-wide model axis forces max_blocks up to a multiple of 4 so every
    # rank owns the same static chunk of page-table columns (the plan only
    # reads the mesh's axis sizes, so a stand-in suffices here)
    fake = SimpleNamespace(axis_names=("data", "model"),
                           devices=np.zeros((1, 4)))
    _, p4 = _plan(page_tokens=8, max_seqs=2, max_seq_len=24, mesh=fake)
    assert p4.model_parallel == 4
    assert p4.max_blocks == 4 and p4.blocks_per_rank == 1


def test_kv_plan_rejects_non_pageable_archs():
    from repro.configs import reduced_config
    from repro.serve import plan_kv_arena

    for arch in ("falcon-mamba-7b", "whisper-base"):
        with pytest.raises(NotImplementedError):
            plan_kv_arena(reduced_config(arch), page_tokens=8)


def test_page_allocator_free_list():
    from repro.serve import KVPageAllocator

    a = KVPageAllocator(6)
    assert a.n_free == 6 and a.n_allocated == 0
    got = a.alloc(4)
    assert len(got) == 4 and len(set(got)) == 4
    assert a.n_free == 2
    with pytest.raises(MemoryError):
        a.alloc(3)
    a.free(got[:2])
    assert a.n_free == 4
    with pytest.raises(ValueError):      # double free
        a.free(got[:1] + got[:1])
    # LIFO recycling: the most recently freed page comes back first
    a2 = KVPageAllocator(3)
    p = a2.alloc(3)
    a2.free([p[1]])
    assert a2.alloc(1) == [p[1]]


# ---------------------------------------------------------------------------
# flash-decode kernel vs references
# ---------------------------------------------------------------------------


def _qkv(rng, b=2, hq=4, hkv=2, l=256, d=16, valid_p=0.7):
    jnp = _jnp()
    q = jnp.asarray(rng.randn(b, hq, 1, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, hkv, l, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, hkv, l, d).astype(np.float32))
    valid = jnp.asarray((rng.rand(b, l) < valid_p).astype(np.int32))
    return q, k, v, valid


def test_flash_decode_deterministic_bitwise(rng):
    """Same input → same bits, twice.  This is the determinism split-KV
    serving relies on (pages are rescored every step)."""
    from repro.kernels.flash_decode.flash_decode import flash_decode_stats_fwd

    q, k, v, valid = _qkv(rng)
    a = flash_decode_stats_fwd(q, k, v, valid, block_k=128, interpret=True)
    b = flash_decode_stats_fwd(q, k, v, valid, block_k=128, interpret=True)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_flash_decode_matches_blockwise_mirror(rng):
    """Kernel vs the op-for-op mirror: identical accumulation order, so
    only XLA-fusion reassociation (~1 ulp/op) separates them.  The bound
    here is ~100x tighter than any algorithmic drift would produce."""
    from repro.kernels.flash_decode import ref
    from repro.kernels.flash_decode.flash_decode import flash_decode_stats_fwd

    q, k, v, valid = _qkv(rng)
    jnp = _jnp()
    ke = jnp.repeat(k, 2, axis=1)
    ve = jnp.repeat(v, 2, axis=1)
    got = flash_decode_stats_fwd(q, k, v, valid, block_k=128, interpret=True)
    want = ref.decode_stats_blockwise(q, ke, ve, valid, block_k=128)
    for g, w, name in zip(got, want, ("acc", "m", "l")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_blockwise_mirror_matches_oracle(rng):
    from repro.kernels.flash_decode import ref

    q, k, v, valid = _qkv(rng, hq=2, hkv=2)
    bw = ref.decode_stats_blockwise(q, k, v, valid, block_k=64)
    one = ref.decode_stats(q, k, v, valid != 0)
    # combine() of each must give the same normalised output
    np.testing.assert_allclose(np.asarray(ref.combine([bw])),
                               np.asarray(ref.combine([one])),
                               rtol=1e-5, atol=1e-6)


def test_split_combine_is_the_full_softmax(rng):
    """The LSE identity: stats over KV splits + combine == one shot —
    through the kernel as well as the oracle."""
    from repro.kernels.flash_decode import flash_decode_stats, combine, ref

    q, k, v, valid = _qkv(rng, l=256)
    full = ref.decode_attention(q, _jnp().repeat(k, 2, 1),
                                _jnp().repeat(v, 2, 1), valid, splits=1)
    parts = []
    for i in range(4):
        sl = slice(i * 64, (i + 1) * 64)
        parts.append(flash_decode_stats(q, k[:, :, sl], v[:, :, sl],
                                        valid[:, sl], block_k=64,
                                        interpret=True))
    np.testing.assert_allclose(np.asarray(combine(parts)),
                               np.asarray(full), rtol=2e-5, atol=2e-6)
    # combine is order-invariant up to float reassociation
    np.testing.assert_allclose(np.asarray(combine(parts[::-1])),
                               np.asarray(combine(parts)),
                               rtol=2e-5, atol=2e-6)


def test_flash_decode_fallback_is_the_oracle(rng):
    """Non-tiling L routes to the one-shot oracle — bitwise, because it IS
    the oracle call."""
    from repro.kernels.flash_decode import flash_decode_stats, ref

    q, k, v, valid = _qkv(rng, l=100)          # 100 % 64 != 0 -> fallback
    jnp = _jnp()
    got = flash_decode_stats(q, k, v, valid, block_k=64)
    want = ref.decode_stats(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                            valid != 0)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_flash_decode_output_wrapper(rng):
    from repro.kernels.flash_decode import flash_decode, ref

    q, k, v, valid = _qkv(rng, l=128)
    jnp = _jnp()
    out = flash_decode(q, k, v, valid, interpret=True)
    want = ref.decode_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                                valid, splits=1)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# paged flash-decode kernel vs the engine's gather + expand + oracle path
# ---------------------------------------------------------------------------


def _paged_case(rng, *, hq, hkv, d, rank=0, pt=8, blocks=4):
    """A filled arena, page table and slots of every kind: lengths 0, 1,
    pt-1, pt and the whole chunk (on rank 1: 0, 1 and either side of the
    chunk's first position), a dead slot, and a slot with unmapped blocks
    (one inside its live span); pages padded to 4 KiB where their K and V
    do not fill it.  Returns the plan, the arena, the table (one layer),
    lengths, validity and q (Hq padded to 16)."""
    import dataclasses
    from types import SimpleNamespace

    import jax.numpy as jnp
    from repro.configs import reduced_config
    from repro.models.attention import padded_heads
    from repro.serve import plan_kv_arena

    base = reduced_config("llama3.2-1b")
    cfg = base.with_(num_layers=1, attn=dataclasses.replace(
        base.attn, num_heads=hq, num_kv_heads=hkv, head_dim=d))
    mp = rank + 1
    mesh = SimpleNamespace(axis_names=("data", "model"),
                           devices=np.zeros((1, mp)))
    plan = plan_kv_arena(cfg, mesh, page_tokens=pt, page_bytes=4096,
                         max_seqs=7, max_seq_len=blocks * mp * pt)
    bpr, first = plan.blocks_per_rank, rank * plan.blocks_per_rank
    full = (first + bpr) * pt - 1
    edge = first * pt or pt            # a block boundary inside the span
    lens = np.array([0, 1, edge - 1, edge, full, full, full], np.int32)
    valid = np.array([1, 1, 1, 1, 1, 0, 1], bool)
    ids = rng.permutation(plan.n_kv_pages)[:7 * plan.max_blocks]
    table = ids.reshape(7, plan.max_blocks, 1).astype(np.int32)
    table[6, first + 1] = table[6, -1] = -1
    pages = jnp.asarray(rng.randn(plan.total_elems), jnp.bfloat16)
    q = jnp.asarray(rng.randn(7, padded_heads(hq), 1, d), jnp.bfloat16)
    return plan, pages, table, lens, valid, q


def _paged_kernel(plan, pages, table, lens, valid, q, rank, group):
    from repro.kernels.flash_decode import paged_decode_stats

    bpr, d = plan.blocks_per_rank, plan.head_dim
    tab = table[:, rank * bpr:(rank + 1) * bpr, 0]
    return paged_decode_stats(
        q, pages.reshape(plan.n_kv_pages, plan.page_stride // d, d), tab,
        lens, valid, rank * bpr, num_kv_heads=plan.num_kv_heads,
        page_tokens=plan.page_tokens, group=group, interpret=True)


@pytest.mark.parametrize("hq,hkv,d,rank", [
    (10, 5, 64, 0),      # GQA, group 2, q heads padded 10 -> 16
    (10, 5, 128, 0),
    (12, 12, 64, 0),     # MHA, padded 12 -> 16
    (8, 2, 128, 0),      # group 4, padded 8 -> 16
    (10, 5, 64, 1),      # rank 1's block chunk
    (12, 12, 128, 1),
], ids=["gqa-pad-d64", "gqa-pad-d128", "mha-pad-d64", "gqa4-pad-d128",
        "rank1-gqa-d64", "rank1-mha-d128"])
def test_paged_decode_matches_gather_expand_oracle(rng, hq, hkv, d, rank):
    """The paged kernel reads each live page in place, once per kv head,
    and gives the statistics of the engine's ``ref`` path (dense gather,
    per-q-head copy, one-shot oracle) at every slot with a live position.
    A slot with none (dead, or short of this rank's chunk) gives
    ``m = NEG_INF``, ``l = 0``, ``acc = 0``: weight 0 in any merge."""
    import jax.numpy as jnp
    from repro.kernels.flash_decode import ref
    from repro.serve.engine import _gather_local_kv, _local_valid

    plan, pages, table, lens, valid, q = _paged_case(rng, hq=hq, hkv=hkv,
                                                     d=d, rank=rank)
    group = hq // hkv
    got = _paged_kernel(plan, pages, table, lens, valid, q, rank, group)

    k, v, tab = _gather_local_kv(pages, plan, 0, jnp.asarray(table), rank)
    ok = _local_valid(plan, tab, jnp.asarray(lens), jnp.asarray(valid), rank)
    kv_idx = jnp.clip(jnp.arange(q.shape[1]) // group, 0, hkv - 1)
    want = ref.decode_stats(q, jnp.take(k, kv_idx, axis=1),
                            jnp.take(v, kv_idx, axis=1), ok)
    live = np.asarray(ok).any(axis=1)
    assert live.sum() == (6 if rank == 0 else 3)
    for g, w, name in zip(got, want, ("acc", "m", "l")):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(g[live], w[live], rtol=1e-5, atol=1e-5,
                                   err_msg=name)
    acc, m, l = (np.asarray(x)[~live] for x in got)
    assert (m == ref.NEG_INF).all() and (l == 0).all() and (acc == 0).all()
    np.testing.assert_allclose(
        np.asarray(ref.combine([got]))[live],
        np.asarray(ref.combine([want]))[live], rtol=1e-5, atol=1e-6)


def test_paged_decode_deterministic_bitwise(rng):
    """The paged kernel, like the dense one: same input → same bits."""
    case = _paged_case(rng, hq=10, hkv=5, d=64)
    a = _paged_kernel(*case, rank=0, group=2)
    b = _paged_kernel(*case, rank=0, group=2)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_kernel_step_refuses_pages_the_chip_cannot_tile():
    """Compiled for the chip, the paged kernel DMAs whole 8-row tiles of
    128 lanes: a plan whose page K rows do not fill them is refused when
    the step is built; interpret mode takes it."""
    import jax
    from jax.sharding import AxisType
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve import plan_kv_arena
    from repro.serve.engine import build_paged_decode_step

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    model = build_model(reduced_config("llama3.2-1b"))    # 2 kv heads of 16
    for pt, tiles in ((8, False), (32, True)):
        plan = plan_kv_arena(model.cfg, mesh, page_tokens=pt,
                             page_bytes=4096, max_seqs=4, max_seq_len=64)
        build_paged_decode_step(model, mesh, plan, interpret=True)
        if tiles:
            build_paged_decode_step(model, mesh, plan, interpret=False)
        else:
            with pytest.raises(ValueError, match="tiles"):
                build_paged_decode_step(model, mesh, plan, interpret=False)


# ---------------------------------------------------------------------------
# paged engine vs the contiguous decode oracle
# ---------------------------------------------------------------------------


def _engine(attn_impl="ref", dtype="float32", cfg=None, **plan_kw):
    import jax
    from jax.sharding import AxisType
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve import PagedDecodeEngine, plan_kv_arena

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = cfg or reduced_config("llama3.2-1b")
    model = build_model(cfg.with_(dtype=dtype))
    params = model.init(jax.random.PRNGKey(0))
    plan_kw.setdefault("page_tokens", 8)
    plan_kw.setdefault("page_bytes", 4096)
    plan_kw.setdefault("max_seqs", 4)
    plan_kw.setdefault("max_seq_len", 64)
    plan = plan_kv_arena(model.cfg, mesh, **plan_kw)
    eng = PagedDecodeEngine(model, mesh, plan, attn_impl=attn_impl,
                            interpret=True)
    return model, params, eng


def _moe_on_kv_pages():
    """Reduced Mixtral (an expert FFN in every layer) with its sliding
    window lifted, so that its K/V rows page: the dense mixer with the
    held-expert layer (all experts held)."""
    import dataclasses

    from repro.configs import reduced_config

    cfg = reduced_config("mixtral-8x7b")
    return cfg.with_(attn=dataclasses.replace(cfg.attn, window=None))


@pytest.mark.parametrize("attn_impl, moe", [
    pytest.param("ref", False, id="ref"),
    pytest.param("kernel", False, id="kernel"),
    pytest.param("ref", True, id="moe-ref"),
    pytest.param("kernel", True, id="moe-kernel")])
def test_paged_matches_contiguous_decode(rng, attn_impl, moe):
    """The tentpole numeric claim: paged flash-decode == the model's own
    contiguous decode_step, token for token, across page boundaries.  With
    expert layers the contiguous step dispatches by capacity, which at one
    token a row drops nothing: the same sum as the held-expert form."""
    import jax.numpy as jnp

    model, params, eng = _engine(attn_impl=attn_impl,
                                 cfg=_moe_on_kv_pages() if moe else None)
    b, steps = eng.plan.max_seqs, 10           # crosses the 8-token page
    state = model.init_decode_state(b, 32)
    for s in range(b):
        eng.admit(s)
    toks = rng.randint(0, model.cfg.vocab_size, (steps, b)).astype(np.int32)
    for t in range(steps):
        tok = jnp.asarray(toks[t])
        got = eng.decode(params, toks[t])
        want, state = model.decode_step(params, tok, state, t, seq_len=32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-3,
            err_msg=f"step {t} ({attn_impl})")


@pytest.mark.parametrize("attn_impl", ["ref", "kernel"])
def test_dense_step_keeps_the_cast_then_split_values(rng, attn_impl,
                                                     monkeypatch):
    """fp32 weights at bf16 compute: the step (rows gathered before they
    are cast, q/k/v behind a barrier before the head split) gives the
    logits and pages of the formula it replaced (the table cast whole
    before the take, the projections split straight away), bit for bit
    over steps that cross a page; and the first step's layer-0 K/V rows
    are ``_split_heads(dense(...))`` of the cast embedding, with RoPE."""
    import jax
    import jax.numpy as jnp

    from repro.models.attention import _split_heads
    from repro.models.common import apply_rope, dense, rmsnorm
    from repro.serve import engine as engine_mod

    model, params, eng = _engine(attn_impl=attn_impl, dtype="bfloat16")

    def cast_then_take(p, tokens, compute_dtype, ctx, global_vocab):
        return jnp.take(p["table"].astype(compute_dtype), tokens, axis=0)

    _, _, old = _engine(attn_impl=attn_impl, dtype="bfloat16")
    assert jax.tree.leaves(params)[0].dtype == jnp.float32
    assert eng.pages.dtype == jnp.bfloat16
    b = eng.plan.max_seqs
    for s in range(b):
        eng.admit(s)
        old.admit(s)
    toks = rng.randint(0, model.cfg.vocab_size, (10, b)).astype(np.int32)
    for t in range(10):
        got = eng.decode(params, toks[t])
        with monkeypatch.context() as mp:   # the first call traces the step
            mp.setattr(engine_mod, "embed", cast_then_take)
            mp.setattr(engine_mod.lax, "optimization_barrier", lambda xs: xs)
            want = old.decode(params, toks[t])
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        np.testing.assert_array_equal(np.asarray(eng.pages, np.float32),
                                      np.asarray(old.pages, np.float32))
        if t == 0:
            cfg, pa = model.cfg, params["blocks"][0]["attn"]
            x = jnp.take(params["embed"]["table"].astype(jnp.bfloat16),
                         jnp.asarray(toks[0])[:, None], axis=0)
            h = rmsnorm(params["blocks"][0]["ln1"], x, cfg.norm_eps)
            pos = jnp.zeros((b, 1), jnp.int32)
            hkv = cfg.attn.num_kv_heads
            k, v, _ = engine_mod._gather_local_kv(
                eng.pages, eng.plan, 0, jnp.asarray(eng.table.table), 0)
            want_k = apply_rope(_split_heads(dense(pa["wk"], h, jnp.bfloat16),
                                             hkv), pos, cfg.attn.rope_theta)
            want_v = _split_heads(dense(pa["wv"], h, jnp.bfloat16), hkv)
            np.testing.assert_array_equal(np.asarray(k[:, :, :1], np.float32),
                                          np.asarray(want_k, np.float32))
            np.testing.assert_array_equal(np.asarray(v[:, :, :1], np.float32),
                                          np.asarray(want_v, np.float32))


def test_engine_slot_lifecycle_and_page_recycling(rng):
    _, params, eng = _engine()
    total = eng.allocator.n_total
    eng.admit(0)
    eng.admit(2)
    assert eng.free_slots() == [1, 3]
    assert eng.allocator.n_allocated == 2 * eng.plan.n_layers
    with pytest.raises(ValueError):
        eng.admit(0)                            # already live
    for _ in range(9):                          # cross the 8-token page
        eng.decode(params, np.zeros((4,), np.int32))
    assert eng.allocator.n_allocated == 2 * 2 * eng.plan.n_layers
    eng.retire(0)
    eng.retire(2)
    assert eng.allocator.n_free == total        # every page came back
    assert not eng.slot_valid.any()
    # retired pages are immediately reusable by a new sequence
    eng.admit(1)
    assert eng.can_admit(16)


# ---------------------------------------------------------------------------
# named scopes and host spans of the engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("attn_impl", ["ref", "kernel"])
def test_op_scopes_name_every_part_of_the_step(attn_impl):
    """``op_scopes`` maps the compiled step's instructions to every scope
    a single-rank step runs, and the kernel's ops (in interpret mode on
    the CPU, the loop it lowers to) to ``flash_decode``.  The kernel path
    reads the pages in place: it has no ``kv_gather`` or ``gqa_expand``."""
    import re

    from repro.obs import ObsConfig, make_obs
    from repro.serve.engine import STEP_SCOPES

    _, params, eng = _engine(attn_impl=attn_impl)
    with pytest.raises(RuntimeError, match="decode step first"):
        eng.op_scopes()
    eng.obs = make_obs(ObsConfig(run_dir=None))
    eng.admit(0)
    eng.decode(params, np.zeros((4,), np.int32))
    ran = eng.obs.bus.counter_total("compiles")
    scopes = eng.op_scopes()
    ran_scopes = set(STEP_SCOPES) - {"attn_merge"}
    if attn_impl == "kernel":
        ran_scopes -= {"kv_gather", "gqa_expand"}
    assert set(scopes.values()) == ran_scopes
    # the map reads the executable the step ran: nothing compiled again
    assert eng.obs.bus.counter_total("compiles") == ran
    text = eng.step.lower(*eng._args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    loops = re.findall(r"%(while[\w.\-]*) = ", entry)
    kernel = {scopes[w] for w in loops}
    assert kernel == ({"flash_decode"} if attn_impl == "kernel" else set())


def test_instruction_scopes_follow_data_to_compiler_made_instructions():
    """An instruction with no scoped ``op_name`` takes its fused
    instructions' scope, else its nearest operand's, else its nearest
    user's; the innermost named part wins."""
    from repro.serve.engine import instruction_scopes

    hlo = """HloModule m, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(fn)/gqa_expand/jit(_take)/neg"}
}

ENTRY %main.9 (w.1: f32[8]) -> f32[8] {
  %w.1 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%w.1)
  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)
  %dot.3 = f32[8]{0} multiply(%copy-done.2, %copy-done.2), metadata={op_name="jit(fn)/qkv_proj/dot_general"}
  %reshape.4 = f32[8]{0} reshape(%dot.3), metadata={op_name="jit(fn)/kv_gather/mlp/reshape"}
  %dus_fusion.5 = f32[8]{0} fusion(%reshape.4), kind=kLoop, calls=%fused_computation.1
  %copy.6 = f32[8]{0} copy(%reshape.4)
  ROOT %add.7 = f32[8]{0} add(%copy.6, %dus_fusion.5), metadata={op_name="jit(fn)/add"}
}
"""
    got = instruction_scopes(hlo)
    assert got["dot.3"] == "qkv_proj"
    assert got["reshape.4"] == "mlp"              # the innermost part
    assert got["dus_fusion.5"] == "gqa_expand"    # its fused instructions
    assert got["copy.6"] == "mlp"                 # its operand
    assert got["copy-done.2"] == "qkv_proj"       # its user
    assert got["add.7"] == "mlp"                  # unscoped op_name
    assert "main.9" not in got and "fused_computation.1" not in got


def test_engine_host_spans_nest_on_the_profiler_clock(tmp_path):
    """With an Obs, a decode step is a ``repro.serve.decode`` span holding
    ``serve.pages``, ``serve.stage`` and ``serve.dispatch`` in turn, its
    step number a label; admit and retire are spans of their own."""
    from conftest import host_trace_events
    from repro.obs import ObsConfig, make_obs

    _, params, eng = _engine()
    eng.obs = make_obs(ObsConfig(run_dir=None))
    eng.admit(0)
    eng.decode(params, np.zeros((4,), np.int32))       # compiled before

    def run():
        eng.admit(1)
        eng.decode(params, np.zeros((4,), np.int32))
        eng.retire(0)

    evs = [e for e in host_trace_events(run, tmp_path / "trace")
           if e[0].startswith("repro.serve.")]
    by = {}
    for e in evs:
        by.setdefault(e[0][len("repro."):], []).append(e)
    assert set(by) == {"serve.admit", "serve.decode", "serve.pages",
                       "serve.stage", "serve.dispatch", "serve.retire"}
    (dec,) = by["serve.decode"]
    assert dec[3]["step"] == 1
    inner = [by[k][0] for k in ("serve.pages", "serve.stage",
                                "serve.dispatch")]
    assert dec[1] <= inner[0][1]
    for a, b in zip(inner, inner[1:]):
        assert a[2] <= b[1]
    assert inner[-1][2] <= dec[2]
    assert by["serve.admit"][0][2] <= dec[1] <= dec[2] <= by["serve.retire"][0][1]
    assert by["serve.retire"][0][3]["slot"] == 0


def test_kv_gauges_are_the_documented_two_and_skipped_without_obs():
    from repro.obs import ObsConfig, make_obs

    _, params, eng = _engine()
    eng.allocator, real = None, eng.allocator
    eng._kv_gauges()                 # NULL_OBS: returns before reading
    eng.allocator = real
    eng.obs = make_obs(ObsConfig(run_dir=None))
    eng.admit(0)
    eng.retire(0)
    assert {n for n, _ in eng.obs.bus.gauges} == {"kv_page_occupancy",
                                                  "kv_page_waste"}


def test_kv_block_counters_count_live_pages_and_skip_without_obs():
    """Per step, ``kv_blocks_read`` adds the live (slot, block, layer)
    pages the kernel fetches and ``kv_blocks_total`` every entry of the
    page table; under ``NULL_OBS`` the engine does no such work."""
    from repro.obs import ObsConfig, make_obs

    _, params, eng = _engine(attn_impl="kernel")
    eng.table, table = None, eng.table
    eng._kv_block_counters()          # NULL_OBS: returns before reading
    eng.table = table
    eng.obs = make_obs(ObsConfig(run_dir=None))
    plan = eng.plan
    eng.admit(0)
    eng.admit(2)
    for t in range(10):
        if t == 4:
            eng.admit(3)
        eng.decode(params, np.zeros((4,), np.int32))
    # slots 0 and 2 read one page a layer at positions 0..7 and two at 8
    # and 9; slot 3 one page at positions 0..5
    bus = eng.obs.bus
    assert bus.counter_total("kv_blocks_read") == \
        (2 * (8 * 1 + 2 * 2) + 6 * 1) * plan.n_layers
    assert bus.counter_total("kv_blocks_total") == \
        10 * plan.max_seqs * plan.max_blocks * plan.n_layers


def test_decode_state_specs_replicate_paged_state():
    """The paged names must dodge the shape[0]==global_batch fallback —
    otherwise slot_len/page_table get scattered over data ranks."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.configs import reduced_config
    from repro.sharding import rules

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = reduced_config("llama3.2-1b")
    state = {
        "pages": jax.ShapeDtypeStruct((1024,), jnp.bfloat16),
        "page_table": jax.ShapeDtypeStruct((4, 8, 2), jnp.int32),
        "slot_len": jax.ShapeDtypeStruct((4,), jnp.int32),
        "slot_valid": jax.ShapeDtypeStruct((4,), jnp.bool_),
    }
    specs = rules.decode_state_specs(state, cfg, mesh, global_batch=4)
    assert all(specs[k] == P() for k in state)


# ---------------------------------------------------------------------------
# lowered HLO: the collective count the dry-run prices
# ---------------------------------------------------------------------------


def test_single_rank_step_lowers_to_zero_collectives():
    import jax

    from repro.launch.roofline import collective_wire_bytes
    from repro.serve.engine import (predicted_collectives_per_token,
                                    predicted_wire_bytes_per_token)

    model, _, eng = _engine()
    assert predicted_collectives_per_token(eng.plan) == 0
    assert predicted_wire_bytes_per_token(eng.plan, model.cfg, 4) == 0.0
    import jax.numpy as jnp

    args = (eng.pages, jax.tree.map(lambda s: s, model.abstract_params()),
            jnp.asarray(eng.table.table), jnp.zeros((4,), jnp.int32),
            jnp.asarray(eng.slot_len), jnp.asarray(eng.slot_valid))
    with eng.mesh:
        txt = eng.step.lower(*args).compile().as_text()
    stats = collective_wire_bytes(txt)
    assert stats.op_counts.get("all-reduce", 0) == 0
    assert sum(stats.op_counts.values()) == 0


SERVE_HLO_R2_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import reduced_config
from repro.models import build_model
from repro.launch.roofline import collective_wire_bytes
from repro.serve import plan_kv_arena
from repro.serve.engine import (build_paged_decode_step,
                                predicted_collectives_per_token,
                                predicted_wire_bytes_per_token)

mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
model = build_model(reduced_config("llama3.2-1b"))
plan = plan_kv_arena(model.cfg, mesh, page_tokens=8, page_bytes=4096,
                     max_seqs=4, max_seq_len=64)
step, pspecs, _ = build_paged_decode_step(model, mesh, plan, attn_impl="ref")
args = (jax.ShapeDtypeStruct((plan.total_elems,), plan.layout.dtype),
        model.abstract_params(),
        jax.ShapeDtypeStruct((plan.max_seqs, plan.max_blocks, plan.n_layers),
                             jnp.int32),
        jax.ShapeDtypeStruct((plan.max_seqs,), jnp.int32),
        jax.ShapeDtypeStruct((plan.max_seqs,), jnp.int32),
        jax.ShapeDtypeStruct((plan.max_seqs,), jnp.bool_))
with mesh:
    txt = step.lower(*args).compile().as_text()
stats = collective_wire_bytes(txt)
n = stats.op_counts.get("all-reduce", 0)
want = predicted_collectives_per_token(plan)
assert want == 2 * plan.n_layers, want
assert n == want, (n, want)                       # zero tolerance
got_b = stats.op_bytes.get("all-reduce", 0.0)
want_b = predicted_wire_bytes_per_token(plan, model.cfg, plan.max_seqs)
assert got_b == want_b, (got_b, want_b)           # zero tolerance

# numeric equivalence R=2 vs R=1: same params, same tokens, same logits
mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                      devices=jax.devices()[:1],
                      axis_types=(AxisType.Auto,) * 2)
plan1 = plan_kv_arena(model.cfg, mesh1, page_tokens=8, page_bytes=4096,
                      max_seqs=4, max_seq_len=64)
from repro.serve import PagedDecodeEngine
params = model.init(jax.random.PRNGKey(0))
e2 = PagedDecodeEngine(model, mesh, plan, attn_impl="ref")
e1 = PagedDecodeEngine(model, mesh1, plan1, attn_impl="ref")
rng = np.random.RandomState(0)
for s in range(4):
    e1.admit(s); e2.admit(s)
for t in range(5):
    tok = rng.randint(0, model.cfg.vocab_size, (4,)).astype(np.int32)
    l1 = np.asarray(e1.decode(params, tok), np.float32)
    l2 = np.asarray(e2.decode(params, tok), np.float32)
    assert np.allclose(l1, l2, rtol=2e-2, atol=2e-3), np.abs(l1 - l2).max()
print("SERVE_HLO_R2_OK")
"""


def test_model_parallel_collective_count_and_equivalence():
    out = run_distributed(SERVE_HLO_R2_SCRIPT, n_devices=2)
    assert "SERVE_HLO_R2_OK" in out


SERVE_SCOPES_R2_SCRIPT = r"""
import re
import jax
from jax.sharding import AxisType
from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import PagedDecodeEngine, plan_kv_arena
from repro.serve.engine import STEP_SCOPES
import numpy as np

mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
model = build_model(reduced_config("llama3.2-1b"))
plan = plan_kv_arena(model.cfg, mesh, page_tokens=8, page_bytes=4096,
                     max_seqs=4, max_seq_len=64)
eng = PagedDecodeEngine(model, mesh, plan, attn_impl="ref")
eng.admit(0)
eng.decode(model.init(jax.random.PRNGKey(0)), np.zeros((4,), np.int32))
scopes = eng.op_scopes()
assert set(scopes.values()) == set(STEP_SCOPES), set(scopes.values())
with mesh:
    txt = eng.step.lower(*eng._args).compile().as_text()
ars = re.findall(r"%([\w.\-]+) = \S+ all-reduce\(", txt)
assert len(ars) == 2 * plan.n_layers, ars
assert {scopes[a] for a in ars} == {"attn_merge"}
print("SERVE_SCOPES_R2_OK")
"""


def test_model_parallel_merge_runs_under_attn_merge():
    """With a model axis of two ranks the step runs every scope, and its
    cross-rank all-reduces map to ``attn_merge``."""
    out = run_distributed(SERVE_SCOPES_R2_SCRIPT, n_devices=2)
    assert "SERVE_SCOPES_R2_OK" in out


SERVE_KERNEL_R2_SCRIPT = r"""
import re
import jax
import numpy as np
from jax.sharding import AxisType
from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import PagedDecodeEngine, plan_kv_arena

mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
model = build_model(reduced_config("llama3.2-1b"))
plan = plan_kv_arena(model.cfg, mesh, page_tokens=8, page_bytes=4096,
                     max_seqs=4, max_seq_len=64)
params = model.init(jax.random.PRNGKey(0))
eng = {i: PagedDecodeEngine(model, mesh, plan, attn_impl=i, interpret=True)
       for i in ("ref", "kernel")}
for e in eng.values():
    for s in (0, 1, 3):
        e.admit(s)
rng = np.random.RandomState(0)
first = plan.blocks_per_rank * plan.page_tokens     # rank 1's first position
for t in range(first + 3):
    tok = rng.randint(0, model.cfg.vocab_size, (4,)).astype(np.int32)
    out = {i: np.asarray(e.decode(params, tok), np.float32)[[0, 1, 3]]
           for i, e in eng.items()}
    assert np.allclose(out["kernel"], out["ref"], rtol=1e-4, atol=1e-5), \
        (t, np.abs(out["kernel"] - out["ref"]).max())
with mesh:
    txt = eng["kernel"].step.lower(*eng["kernel"]._args).compile().as_text()
assert len(re.findall(r" all-reduce\(", txt)) == 2 * plan.n_layers
print("SERVE_KERNEL_R2_OK")
"""


def test_model_parallel_kernel_reads_each_ranks_chunk():
    """On two ranks the paged kernel scores each rank's own block chunk:
    logits match the ``ref`` path's at positions on both ranks, with the
    same two collectives a layer."""
    out = run_distributed(SERVE_KERNEL_R2_SCRIPT, n_devices=2)
    assert "SERVE_KERNEL_R2_OK" in out


# ---------------------------------------------------------------------------
# gathered serving guard (satellite: family check covered every family)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "hymba-1.5b",
                                  "whisper-base"])
def test_gathered_serving_rejects_non_decoder_only(arch):
    """ssm / hybrid / audio-frontend families must refuse gathered serving
    at BUILD time (the old check only caught encdec, only in prefill)."""
    import jax
    from jax.sharding import AxisType
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.runtime.serve_step import build_decode_step, build_prefill
    from repro.configs.base import ShapeConfig

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    model = build_model(reduced_config(arch))
    shp = ShapeConfig("serve_test", 16, 2, "decode")
    with pytest.raises(NotImplementedError, match="decoder-only"):
        build_prefill(model, mesh, shp, weight_mode="gathered")
    with pytest.raises(NotImplementedError, match="decoder-only"):
        build_decode_step(model, mesh, shp, weight_mode="gathered")


def test_gathered_serving_still_builds_for_decoder_only():
    import jax
    from jax.sharding import AxisType
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.runtime.serve_step import build_decode_step
    from repro.configs.base import ShapeConfig

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    model = build_model(reduced_config("llama3.2-1b"))
    shp = ShapeConfig("serve_test", 16, 2, "decode")
    step, pspecs, sspecs = build_decode_step(model, mesh, shp,
                                             weight_mode="gathered")
    assert "groups" in pspecs


# ---------------------------------------------------------------------------
# scheduler: continuous vs static batching
# ---------------------------------------------------------------------------


class _FakeEngine:
    """Step-exact stand-in: same slot/page accounting as the real engine,
    no device work.  Lets the ≥2× throughput claim be asserted in
    milliseconds; bench_serve measures it on the real engine."""

    class _Cfg:
        vocab_size = 512

    class _Model:
        cfg = None

    def __init__(self, max_seqs=4, page_tokens=8, max_seq_len=96,
                 n_layers=2):
        from repro.serve import KVPageAllocator

        class Plan:
            pass

        self.plan = Plan()
        self.plan.max_seqs = max_seqs
        self.plan.page_tokens = page_tokens
        self.plan.n_layers = n_layers
        self.model = self._Model()
        self.model.cfg = self._Cfg()
        n_blocks = -(-max_seq_len // page_tokens)
        self.allocator = KVPageAllocator(max_seqs * n_blocks * n_layers)
        self.slot_valid = np.zeros((max_seqs,), bool)
        self.slot_len = np.zeros((max_seqs,), np.int32)
        self._pages = {}

    def free_slots(self):
        return [i for i in range(self.plan.max_seqs)
                if not self.slot_valid[i]]

    def pages_for(self, n_tokens):
        return -(-n_tokens // self.plan.page_tokens) * self.plan.n_layers

    def can_admit(self, n_tokens):
        return (bool(self.free_slots())
                and self.allocator.n_free >= self.pages_for(n_tokens))

    def admit(self, slot):
        self.slot_valid[slot] = True
        self.slot_len[slot] = 0
        self._pages[slot] = self.allocator.alloc(self.plan.n_layers)

    def retire(self, slot):
        self.allocator.free(self._pages.pop(slot))
        self.slot_valid[slot] = False
        self.slot_len[slot] = 0

    def decode(self, params, token):
        for s in np.nonzero(self.slot_valid)[0]:
            if self.slot_len[s] % self.plan.page_tokens == 0 \
                    and self.slot_len[s] > 0:
                self._pages[int(s)] += self.allocator.alloc(
                    self.plan.n_layers)
        self.slot_len[self.slot_valid] += 1
        return np.zeros((self.plan.max_seqs, 512), np.float32)


def test_continuous_batching_beats_static_2x():
    """The acceptance ratio on the mixed-length trace: shorts turn their
    slots around while longs keep decoding, so continuous ≥ 2× static."""
    from repro.serve import ServeScheduler, mixed_trace

    reqs = mixed_trace(groups=4, slots=4, long_len=64, short_len=4)
    res = {}
    for policy in ("continuous", "static"):
        sched = ServeScheduler(_FakeEngine(), policy=policy)
        res[policy] = sched.run(None, reqs)
    assert res["continuous"]["generated_tokens"] == \
        res["static"]["generated_tokens"] == sum(r.decode_len for r in reqs)
    ratio = (res["continuous"]["tokens_per_step"]
             / res["static"]["tokens_per_step"])
    assert ratio >= 2.0, res
    # static pays exactly groups * the long request's step count
    assert res["static"]["steps"] == 4 * 64
    assert res["continuous"]["mean_live_slots"] > \
        res["static"]["mean_live_slots"]


def test_scheduler_rejects_bad_policy_and_stalls():
    from repro.serve import Request, ServeScheduler

    with pytest.raises(ValueError, match="policy"):
        ServeScheduler(_FakeEngine(), policy="dynamic")
    with pytest.raises(ValueError):
        Request(0, prompt_len=0, decode_len=4)
    # a request that can never fit must raise, not spin — and the guard
    # trip must be visible on the bus as a serve_stall counter
    from repro.obs import ObsConfig, make_obs

    obs = make_obs(ObsConfig(run_dir=None))
    sched = ServeScheduler(_FakeEngine(max_seqs=2, max_seq_len=16), obs=obs)
    with pytest.raises(RuntimeError, match="stalled"):
        sched.run(None, [Request(0, 1, 1000)])
    assert obs.bus.counter_total("serve_stall") == 1
    assert obs.bus.counter_value("serve_stall",
                                 reason="arena_too_small") == 1

    # the max_steps guard trips the same counter under its own label
    obs2 = make_obs(ObsConfig(run_dir=None))
    sched2 = ServeScheduler(_FakeEngine(), obs=obs2)
    with pytest.raises(RuntimeError, match="max_steps"):
        sched2.run(None, [Request(0, 1, 64)], max_steps=3)
    assert obs2.bus.counter_value("serve_stall", reason="max_steps") == 1


def test_scheduler_policies_agree_on_the_real_engine(rng):
    """End-to-end with the real jitted step: both policies finish the
    trace, recycle every page, and never recompile mid-run."""
    from repro.serve import ServeScheduler, mixed_trace

    reqs = mixed_trace(groups=2, slots=3, long_len=10, short_len=3)
    for policy in ("continuous", "static"):
        _, params, eng = _engine(max_seqs=3, max_seq_len=16)
        out = ServeScheduler(eng, policy=policy).run(params, reqs)
        assert out["generated_tokens"] == sum(r.decode_len for r in reqs)
        assert eng.allocator.n_free == eng.allocator.n_total
        assert not eng.slot_valid.any()


def test_scheduler_steps_are_the_engine_decode_spans():
    """The scheduler adds no span of its own around a step: the engine's
    ``serve.decode`` spans (one per step) and the ``compiles`` counter
    (one compile for the whole run) are what its obs records."""
    from repro.obs import ObsConfig, make_obs
    from repro.serve import ServeScheduler, mixed_trace

    reqs = mixed_trace(groups=1, slots=3, long_len=6, short_len=2)
    _, params, eng = _engine(max_seqs=3, max_seq_len=16)
    eng.obs = make_obs(ObsConfig(run_dir=None))
    out = ServeScheduler(eng).run(params, reqs)
    spans = eng.obs.bus.spans
    assert "decode_step" not in spans
    assert len(spans["serve.decode"]) == out["steps"]
    assert eng.obs.bus.counter_value("compiles", fun="jit(fn)") == 1
