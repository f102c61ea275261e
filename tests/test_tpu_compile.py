"""The main path's Pallas kernels compile for a v5e chip at real shapes.

Interpret mode (every other kernel test) never meets the Mosaic compiler,
which refuses block shapes and memory use the interpreter accepts.  These
tests compile each kernel of the serving and gradient-arena paths for a
*described* v5e:2x2 topology — no chip is attached, nothing runs — and
check the compiled program holds the kernel (``tpu_custom_call``), i.e.
that the main-path shapes take the kernel and not the jnp fallback.  One
more test holds the ring gradient reduction to a compile time in seconds
at a published-width parameter shape.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.
"""

import os
import time

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.ring import RingConfig, ring_all_gather, ring_reduce_scatter
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.pack import ops as pack_ops
from repro.kernels.pack_quant import ops as pq_ops

PAGE = 2 * 2**20 // 4          # one 2 MiB arena page of fp32 elements
BUCKET = 2**20                 # 1 MiB-element quantized bucket
QBLOCK = 512                   # the int8 wire codec's block


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flash_decode(sds):
    # the dense single-shard kernel: B=8 slots, 32 q heads with the 8 GQA
    # kv heads expanded per q head, 1024 key positions, head_dim 64, bf16
    b, hq, length, d = 8, 32, 1024, 64
    args = (sds((b, hq, 1, d), jnp.bfloat16),
            sds((b, hq, length, d), jnp.bfloat16),
            sds((b, hq, length, d), jnp.bfloat16),
            sds((b, length), jnp.bool_))
    return (lambda q, k, v, m: fd_ops.flash_decode_stats(
        q, k, v, m, interpret=False)), args


def _paged_decode(sds):
    # the paged engine's call at the Phi-3-medium serving cell: 48 slots,
    # 48 q heads (40 padded) over 10 kv heads, head_dim 128, 128-token
    # pages of 14 blocks over 5 layers' arena, read in place
    b, hq, hkv, d, pt, blocks = 48, 48, 10, 128, 128, 14
    n_pages, page_rows = b * blocks * 5, 2 * hkv * pt
    args = (sds((b, hq, 1, d), jnp.bfloat16),
            sds((n_pages, page_rows, d), jnp.bfloat16),
            sds((b, blocks), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.bool_), sds((), jnp.int32))
    return (lambda *a: fd_ops.paged_decode_stats(
        *a, num_kv_heads=hkv, page_tokens=pt, group=4, interpret=False)), args


def _mla_decode(sds):
    # the latent kernel at Moonlight-16B-A3B's serving cell: 48 slots, 16
    # heads over one latent row of 512 + 64 a token, 128-token pages of 32
    # blocks (two layers' arena here), read in place
    b, hq, r, dr, pt, blocks = 48, 16, 512, 64, 128, 32
    n_pages, page_rows = b * blocks * 2, (r + dr) * pt // 128
    args = (sds((b, hq, r), jnp.float32), sds((b, hq, dr), jnp.float32),
            sds((n_pages, page_rows, 128), jnp.bfloat16),
            sds((b, blocks), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.bool_), sds((), jnp.int32))
    return (lambda *a: fd_ops.mla_decode_stats(
        *a, page_tokens=pt, rope_pack=2, scale=192 ** -0.5,
        interpret=False)), args


def _write_flat(sds):
    return (lambda a, s: pack_ops.write_flat(a, s, PAGE, interpret=False),
            (sds((4 * PAGE,), jnp.float32), sds((PAGE,), jnp.float32)))


def _read_flat(sds):
    return (lambda a: pack_ops.read_flat(a, PAGE, PAGE, interpret=False),
            (sds((4 * PAGE,), jnp.float32),))


def _write_quant_flat(sds):
    return (lambda a, s: pq_ops.write_quant_flat(
        a, s, BUCKET, 3 * BUCKET, QBLOCK, interpret=False),
        (sds((4 * BUCKET,), jnp.int8), sds((BUCKET,), jnp.float32)))


def _read_dequant_flat(sds):
    return (lambda a: pq_ops.read_dequant_flat(
        a, BUCKET, BUCKET, 3 * BUCKET, QBLOCK, interpret=False),
        (sds((4 * BUCKET,), jnp.int8),))


@pytest.mark.parametrize("case", [_flash_decode, _paged_decode, _mla_decode,
                                  _write_flat,
                                  _read_flat,
                                  _write_quant_flat, _read_dequant_flat],
                         ids=lambda c: c.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(case, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = case(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ring_reduce_of_a_tiled_param_compiles_in_seconds(topo):
    """ZeRO-1's gradient path at llama3.2-1b's embedding shape: flatten a
    128256x2048 fp32 array, ring reduce-scatter and all-gather it over
    four v5e chips, reshape it back.  With the reshapes fused into the
    ring's channel slicing the TPU compiler took about 400 s; with the
    flat buffer materialised, about 2 s."""
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,),
                         devices=topo.devices[:4])
    cfg = RingConfig(chunks=2, bidirectional=True)

    def reduce(w):
        shard = ring_reduce_scatter(w.reshape(-1), "data", cfg)
        return w + ring_all_gather(shard, "data", cfg).reshape(w.shape)

    fn = jax.jit(jax.shard_map(reduce, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
    arg = jax.ShapeDtypeStruct((128256, 2048), jnp.float32,
                               sharding=NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    fn.lower(arg).compile()
    assert time.perf_counter() - t0 < 60


def _compiled_step_text(model, mesh, plan) -> str:
    """The paged decode step (kernel path) compiled for ``mesh``'s described
    chip, as the compiler's text."""
    from repro.serve.engine import build_paged_decode_step

    step, _, _ = build_paged_decode_step(model, mesh, plan,
                                         attn_impl="kernel", interpret=False)
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    s = plan.max_seqs
    args = (sds((plan.total_elems,), plan.layout.dtype),
            jax.tree.map(lambda a: sds(a.shape, a.dtype),
                         model.abstract_params()),
            sds((s, plan.max_blocks, plan.n_layers), jnp.int32),
            sds((s,), jnp.int32), sds((s,), jnp.int32), sds((s,), jnp.bool_))
    return step.lower(*args).compile().as_text()


def test_paged_step_kernel_maps_to_the_flash_decode_scope(topo):
    """The paged decode step compiled for a v5e keeps the kernel, and the
    kernel's ``tpu_custom_call`` maps to the ``flash_decode`` scope: the
    name a chip trace gives the kernel finds its scope."""
    import dataclasses
    import re

    from jax.sharding import Mesh

    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve import plan_kv_arena
    from repro.serve.engine import instruction_scopes

    base = reduced_config("llama3.2-1b")
    cfg = base.with_(d_model=256, dtype="bfloat16",
                     attn=dataclasses.replace(base.attn, num_heads=4,
                                              num_kv_heads=2, head_dim=128))
    model = build_model(cfg)
    mesh = Mesh([[topo.devices[0]]], ("data", "model"))
    plan = plan_kv_arena(cfg, mesh, page_tokens=128, page_bytes=2**16,
                         max_seqs=8, max_seq_len=512)
    text = _compiled_step_text(model, mesh, plan)
    kernels = re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                         r'"tpu_custom_call"', text)
    assert len(kernels) == plan.n_layers
    scopes = instruction_scopes(text)
    assert {scopes[k] for k in kernels} == {"flash_decode"}


def test_latent_step_kernel_is_named_mla_decode(topo):
    """The paged decode step over latent pages compiled for a v5e keeps one
    latent kernel a layer, its instruction named ``mla_decode`` (the name
    the benchmark's reader finds in a chip trace) in the ``mla_decode``
    scope, and the step's instructions map to every scope of its block."""
    import dataclasses
    import re

    from jax.sharding import Mesh

    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve import plan_kv_arena
    from repro.serve.engine import LATENT_STEP_SCOPES, instruction_scopes

    base = reduced_config("moonlight-16b-a3b")
    cfg = base.with_(d_model=256, dtype="bfloat16", param_dtype="bfloat16",
                     num_layers=2,
                     attn=dataclasses.replace(base.attn, kv_lora_rank=512,
                                              qk_rope_head_dim=64))
    model = build_model(cfg)
    mesh = Mesh([[topo.devices[0]]], ("data", "model"))
    plan = plan_kv_arena(cfg, mesh, page_tokens=128, page_bytes=2**17,
                         max_seqs=8, max_seq_len=512)
    text = _compiled_step_text(model, mesh, plan)
    kernels = re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                         r'"tpu_custom_call"', text)
    assert len(kernels) == plan.n_layers
    assert {k.split(".")[0] for k in kernels} == {"mla_decode"}
    scopes = instruction_scopes(text)
    assert {scopes[k] for k in kernels} == {"mla_decode"}
    assert set(scopes.values()) == set(LATENT_STEP_SCOPES) - {"kv_gather"}


def test_dense_paged_step_reads_each_weight_in_place(topo):
    """The dense paged decode step at Phi-3-medium's published widths (two
    of its layers, 48 slots, fp32 weights, bf16 compute) compiled for a
    v5e writes no step-sized copy of a weight: every instruction of the
    entry computation with as many elements as a weight is that weight's
    parameter, a piece of its asynchronous prefetch (same dtype, no
    conversion), or the dot or gather fusion that consumes it.  The
    ``embed`` scope holds nothing of the table's size but the table: the
    rows are gathered before they are cast."""
    import math
    import re

    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.configs.base import AttnConfig
    from repro.models import build_model
    from repro.serve import plan_kv_arena
    from repro.serve.engine import instruction_scopes
    from repro.serve.kv import kv_page_payload_elems

    cfg = get_config("phi3-medium-14b").with_(
        num_layers=2, d_model=5120, d_ff=17920, vocab_size=32064,
        attn=AttnConfig(num_heads=40, num_kv_heads=10, head_dim=128,
                        rope_theta=1e4),
        tie_embeddings=False, param_dtype="float32", dtype="bfloat16")
    model = build_model(cfg)
    mesh = Mesh([[topo.devices[0]]], ("data", "model"))
    plan = plan_kv_arena(model.cfg, mesh, page_tokens=128,
                         page_bytes=kv_page_payload_elems(model.cfg, 128) * 2,
                         max_seqs=48, max_seq_len=1792)
    text = _compiled_step_text(model, mesh, plan)
    params = model.abstract_params()

    weights = [a for a in jax.tree.leaves(params) if a.ndim == 2]
    assert {a.dtype for a in weights} == {jnp.dtype("float32")}
    sizes = {math.prod(a.shape) for a in weights}
    table = math.prod(params["embed"]["table"].shape)
    inst = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]"
                      r"(?:\{[^}]*\})?\s+([\w\-]+)\(")
    entry = text[text.index("\nENTRY"):]
    copies, by_name = [], {}
    for line in entry.splitlines()[1:]:
        m = inst.match(line)
        if m is None:
            continue
        name, dtype, dims, opcode = m.groups()
        n = math.prod(int(x) for x in dims.split(",") if x)
        by_name[name] = (n, opcode)
        if n not in sizes:
            continue
        kind = re.search(r"\bkind=(\w+)", line)
        target = re.search(r'custom_call_target="(\w+)"', line)
        consumer = opcode == "fusion" and kind and \
            kind.group(1) in ("kOutput", "kCustom")
        prefetch = dtype == "f32" and (
            opcode in ("parameter", "bitcast", "copy-start", "copy-done")
            or (opcode == "custom-call" and target
                and target.group(1) == "ConcatBitcast"))
        if not (consumer or prefetch):
            copies.append(line.strip()[:160])
    assert copies == []
    scopes = instruction_scopes(text)
    in_embed = [k for k, (n, op) in by_name.items()
                if scopes.get(k) == "embed" and n == table
                and op != "parameter"]
    assert in_embed == []
