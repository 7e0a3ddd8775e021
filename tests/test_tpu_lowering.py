"""The cheap gate that keeps chip time from being spent on trace-time errors.

Every kernel an ``"auto"`` default selects on TPU is taken as far towards the
chip as a CPU host can take it, at the shapes ``chip_smoke.py`` and the GPT /
serving experiments use:

- cross-lowered for TPU (``lower(lowering_platforms=("tpu",))``) — the
  Pallas→Mosaic MLIR gate that refused the flash kernel's (1, T) mask and
  lse blocks;
- where the installed libtpu can describe a v5e topology without a chip,
  AOT-compiled for it — libtpu's own Mosaic compiler, the stage that refuses
  unsupported relayouts, run without device time;
- and the reducer path the trainer takes on TPU,
  ``PowerSGDReducer(orthogonalize_impl="pallas")`` inside ``shard_map``, is
  stepped on the CPU mesh in interpret mode.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from network_distributed_pytorch_tpu.ops import gated_delta, gated_delta_frame, qk_rope
from network_distributed_pytorch_tpu.ops.flash_attention import flash_attention
from network_distributed_pytorch_tpu.ops.grouped_matmul import grouped_matmul
from network_distributed_pytorch_tpu.ops.pallas_orthogonalize import (
    orthogonalize_pallas,
)
from network_distributed_pytorch_tpu.ops.rows_to_tokens import rows_of_tokens, tokens_from_rows

# (b, t, h, d[, key/value heads[, window[, value width]]]), dtype, causal, padded mask — chip_smoke's
# DistilBERT-base attention (bf16 and fp32), GPT-2-small at its context, gpt_lm's full
# preset (t=64), a serve prefill length that is no multiple of 128, and the
# benchmark cells' own: imdb_psgd16_b16 (one 512x512 tile a head) and
# nemotron_psgd16_t8k (512x512 tiles, K and V whole: the VMEM request; with
# 32 key/value heads and with the model's 2, read in place),
# trinity_psgd16_t8k's sliding layers (32 heads over 4, a window of 2048:
# both loop bounds), qwen3next_psgd16_t8k's full layer (16 heads of 256 over
# 2: two lane tiles a head, the VMEM of T = 16,384 at 128), lfm2_psgd16_t8k's
# attention layer (32 heads of 64 over 8: grouped heads narrower than a lane
# block, so the fold at T = 8192, one 64-lane head's K and V whole in VMEM),
# mellum2_psgd16_t8k's sliding layers (trinity's heads, a window of 1024: 8
# windows, a band two 512-tiles wide), phi4flash_psgd16_t8k's differential
# attention (the four attentions of a layer as one call, two softmaxes a pair:
# 40 query heads of 64 over 20 key heads of 64 and 20 value heads of 128, so the
# fold with a value block of its own width; causal in the full and cross layers,
# a window of 512 in the sliding one: 16 windows, a band one or two 512-tiles
# wide; and the call it was until PR 49, 80 stacked heads of 64 over 40, the
# fold at one width), a value head of 256 beside a head of 128 read in place
# (one head a block at two widths), and a head width no lane block serves (the
# fold)
FLASH_CASES = [
    pytest.param((16, 256, 12, 64), jnp.bfloat16, False, True, id="distilbert-bf16"),
    pytest.param((16, 256, 12, 64), jnp.float32, False, True, id="distilbert-fp32"),
    pytest.param((8, 1024, 12, 64), jnp.bfloat16, True, False, id="gpt-1024-causal"),
    pytest.param((8, 64, 12, 64), jnp.float32, True, False, id="gpt_lm-64-causal"),
    pytest.param((2, 96, 12, 64), jnp.float32, True, False, id="prefill-96-causal"),
    pytest.param((48, 512, 12, 64), jnp.bfloat16, False, True, id="imdb-48x512"),
    pytest.param((1, 8192, 32, 128), jnp.bfloat16, True, False, id="nemotron-8192-causal"),
    pytest.param((1, 8192, 32, 128, 2), jnp.bfloat16, True, False, id="nemotron-8192-gqa"),
    pytest.param((1, 8192, 32, 128, 4, 2048), jnp.bfloat16, True, False, id="trinity-8192-window-2048"),
    pytest.param((1, 8192, 16, 256, 2), jnp.bfloat16, True, False, id="qwen3next-8192-head-256-gqa"),
    pytest.param((1, 8192, 32, 64, 8), jnp.bfloat16, True, False, id="lfm2-8192-head-64-gqa-fold"),
    pytest.param((1, 8192, 32, 128, 4, 1024), jnp.bfloat16, True, False, id="mellum-8192-window-1024"),
    pytest.param((1, 8192, 80, 64, 40), jnp.bfloat16, True, False, id="phi4flash-8192-stacked-64-fold"),
    pytest.param((1, 8192, 80, 64, 40, 512), jnp.bfloat16, True, False, id="phi4flash-8192-stacked-64-window-512"),
    pytest.param((1, 8192, 40, 64, 20, None, 128), jnp.bfloat16, True, False, id="phi4flash-8192-64-over-value-128-fold"),
    pytest.param((1, 8192, 40, 64, 20, 512, 128), jnp.bfloat16, True, False, id="phi4flash-8192-64-over-value-128-window-512"),
    pytest.param((1, 4096, 8, 128, 2, None, 256), jnp.bfloat16, True, False, id="value-256-over-128-gqa"),
    pytest.param((4, 512, 3, 64), jnp.bfloat16, False, True, id="fold-3x64"),
]
# P-factor shapes: DistilBERT-base at rank 16 (chip_smoke), GPT-2 at rank 4
ORTHOGONALIZE_SHAPES = [
    (30522, 16), (3072, 16), (768, 16), (512, 16), (768, 2), (50257, 4),
    (2048, 4),
]
# (k, n) of the routed experts' products over a chunk of 8192 sorted rows and
# 8 held experts, bf16: nemotron_psgd16_t8k's two (1856 = 14.5 x 128 is one
# tile, as the contraction and as the output) and trinity_psgd16_t8k's; with
# a third number, that many held experts: qwen3next_psgd16_t8k's 16 of width 512;
# lfm2_psgd16_t8k's 8 of width 1536 (three 512-tiles); with a fourth, that many
# rows a chunk: mellum2_psgd16_t8k's 16 of width 896 = 7 x 128 over 2304 = 18 x
# 128 (one 896-tile, three 768-tiles) at the 24,576 rows parallel/moe.chunk_rows
# gives its load
GROUPED_MATMUL_SHAPES = [
    (2688, 1856), (1856, 2688), (2048, 1024), (1024, 2048), (2048, 512, 16), (512, 2048, 16),
    (2048, 1536), (1536, 2048), (2304, 896, 16, 24576), (896, 2304, 16, 24576),
]
# (rows, d, runs) of the expert layers' adds of rows into 8192 tokens: mellum2_psgd16_t8k's
# 24,576-row chunk over 16 held experts and nemotron_psgd16_t8k's 8,192 over 8 (its width,
# 21 x 128, the widest of the five cells)
ROWS_TO_TOKENS_SHAPES = [(24576, 2304, 16), (8192, 2688, 8)]


def _flash_fns(shape, dtype, causal, masked):
    b, t, h, d = shape[:4]
    hkv, window, dv = (*shape[4:], *(h, None, d)[len(shape) - 4:])
    heads = lambda n, width: jax.ShapeDtypeStruct((b, t, n, width), dtype)
    args = [heads(h, d), heads(hkv, d), heads(hkv, dv)]
    if masked:
        args.append(jax.ShapeDtypeStruct((b, t), jnp.float32))

    def forward(q, k, v, mask=None):
        return flash_attention(q, k, v, mask=mask, causal=causal, window=window)

    def loss(q, k, v, mask=None):
        return forward(q, k, v, mask).astype(jnp.float32).sum()

    return args, {"forward": forward, "grad": jax.grad(loss, argnums=(0, 1, 2))}


@pytest.mark.parametrize("shape,dtype,causal,masked", FLASH_CASES)
@pytest.mark.parametrize("which", ["forward", "grad"])
def test_flash_attention_lowers_for_tpu(shape, dtype, causal, masked, which):
    args, fns = _flash_fns(shape, dtype, causal, masked)
    lowered = jax.jit(fns[which]).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.mark.parametrize("shape", ORTHOGONALIZE_SHAPES, ids=str)
def test_pallas_orthogonalize_lowers_for_tpu(shape):
    arg = jax.ShapeDtypeStruct(shape, jnp.float32)
    lowered = jax.jit(orthogonalize_pallas).trace(arg).lower(
        lowering_platforms=("tpu",)
    )
    assert "tpu_custom_call" in lowered.as_text()


def _grouped_matmul_fns(shape):
    k, n, held, rows = (*shape, *(8, 8192)[len(shape) - 2:])
    args = [
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, k, n), jnp.bfloat16),
        jax.ShapeDtypeStruct((held,), jnp.int32),
    ]
    # interpret=False: the kernels, whatever backend traces them
    forward = lambda lhs, rhs, sizes: grouped_matmul(lhs, rhs, sizes, interpret=False)
    loss = lambda lhs, rhs, sizes: jnp.sum(jnp.sin(forward(lhs, rhs, sizes)))
    return args, {"forward": forward, "grad": jax.grad(loss, argnums=(0, 1))}


@pytest.mark.parametrize("shape", GROUPED_MATMUL_SHAPES, ids=str)
def test_grouped_matmul_lowers_for_tpu_inside_shard_map(shape):
    """Where every training step runs: the kernels' outputs declare how they
    vary over the mesh (the library's own kernels do not, and are refused
    here), rows and group sizes a worker's own, the matrices shared."""
    from jax.sharding import Mesh, PartitionSpec as P

    args, fns = _grouped_matmul_fns(shape)
    args = [jax.ShapeDtypeStruct((2,) + a.shape, a.dtype) for a in args]
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def worker(lhs, rhs, sizes):
        d_lhs, d_rhs = fns["grad"](lhs[0], rhs[0], sizes[0])
        return d_lhs[None], d_rhs[None]

    sharded = jax.shard_map(worker, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    text = jax.jit(sharded).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3


def _rows_to_tokens_fns(shape, t=8192):
    """The layer's two uses: fp32 rows added into their tokens (the kernel
    forward, its cotangent the gather) and bf16 tokens gathered to their rows
    (the kernel backward, on a bf16 cotangent)."""
    rows, d, runs = shape
    args = [
        jax.ShapeDtypeStruct((rows, d), jnp.float32), jax.ShapeDtypeStruct((t, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((rows,), jnp.int32), jax.ShapeDtypeStruct((runs,), jnp.int32),
    ]
    # interpret=False: the kernel, whatever backend traces it
    forward = lambda part, x, token, sizes: tokens_from_rows(part, token, sizes, t, interpret=False)

    def loss(part, x, token, sizes):
        rows_in = rows_of_tokens(x, token, sizes, interpret=False).astype(jnp.float32)
        return jnp.sum(jnp.sin(forward(part * rows_in, x, token, sizes)))

    return args, {"forward": forward, "grad": jax.grad(loss, argnums=(0, 1))}


@pytest.mark.parametrize("shape", ROWS_TO_TOKENS_SHAPES, ids=str)
def test_tokens_from_rows_lowers_for_tpu_inside_shard_map(shape):
    """Where every training step runs: the kernel's output declares how it
    varies over the mesh; rows, tokens and sizes a worker's own."""
    from jax.sharding import Mesh, PartitionSpec as P

    args, fns = _rows_to_tokens_fns(shape)
    args = [jax.ShapeDtypeStruct((2,) + a.shape, a.dtype) for a in args]
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def worker(*operands):
        d_part, d_x = fns["grad"](*(operand[0] for operand in operands))
        return d_part[None], d_x[None]

    sharded = jax.shard_map(worker, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    text = jax.jit(sharded).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 2  # forward, and the gather's cotangent


def _chunk_local_fns(bsz=1, t=8192, hk=16, hv=32, d=128, chunk=64, dtype=jnp.bfloat16):
    """The gated delta rule's chunk-local stage at qwen3next_psgd16_t8k's
    shapes: one sequence of 8192, 16 key and 32 value heads of 128, chunks of
    64, bf16; and the whole rule on a chip (``default_backend`` steered)."""
    step_values = jax.ShapeDtypeStruct((bsz, t // chunk, hk, hv // hk, chunk), jnp.float32)
    k, v = jax.ShapeDtypeStruct((bsz, t, hk, d), dtype), jax.ShapeDtypeStruct((bsz, t, hv, d), dtype)
    # interpret=False: the kernels, whatever backend traces them
    stage = lambda q, k, v, gamma, beta: gated_delta.chunk_local(q, k, v, gamma, beta, chunk, interpret=False)
    loss = lambda *a: sum(jnp.sum(jnp.sin(x.astype(jnp.float32))) for x in stage(*a))
    per_step = jax.ShapeDtypeStruct((bsz, t, hv), jnp.float32)
    rule = lambda *a: jnp.sum(jnp.sin(gated_delta.gated_delta_rule(*a, chunk=chunk).astype(jnp.float32)))
    return (
        [k, k, v, step_values, step_values], {"forward": stage, "grad": jax.grad(loss, argnums=range(5))},
        [k, k, v, per_step, per_step], jax.grad(rule, argnums=range(5)),
    )


def test_gated_delta_chunk_local_lowers_for_tpu_inside_shard_map():
    """Both kernels' outputs declare how they vary over the mesh, as every
    training step's ``shard_map(check_vma=True)`` asks."""
    from jax.sharding import Mesh, PartitionSpec as P

    args, fns, _, _ = _chunk_local_fns(bsz=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    sharded = jax.shard_map(fns["grad"], mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    text = jax.jit(sharded).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2


def _frame_fns(bsz=1, t=8192, hk=16, r=2, d=128, dtype=jnp.bfloat16):
    """What frames the rule at qwen3next_psgd16_t8k's shapes: ``qkvz`` (1,
    8192, 12288) bf16 in the grouped column order, the conv's four taps, g and
    beta; both passes as kernels whatever traces them, round a stand-in for
    the rule that reads every operand, and the gradient of every input."""
    qkvz = jax.ShapeDtypeStruct((bsz, t, hk * (2 + 2 * r) * d), dtype)
    conv_kernel = jax.ShapeDtypeStruct((4, hk * (2 + r) * d), jnp.float32)
    norm_scale = jax.ShapeDtypeStruct((d,), jnp.float32)
    per_step = jax.ShapeDtypeStruct((bsz, t, hk * r), jnp.float32)

    def rule(q, k, v, g, beta):
        by_key = v.reshape(v.shape[:2] + (hk, r, d)) * (q * k)[:, :, :, None]
        return by_key.reshape(v.shape) * (beta * jnp.exp(g))[..., None].astype(v.dtype)

    def forward(qkvz, conv_kernel, norm_scale, g, beta):
        return gated_delta_frame.framed_rule(rule, qkvz, conv_kernel, norm_scale, g, beta, 1e-6, hk, r, d, d, interpret=False)

    loss = lambda *a: jnp.sum(jnp.sin(forward(*a).astype(jnp.float32)))
    return [qkvz, conv_kernel, norm_scale, per_step, per_step], {"forward": forward, "grad": jax.grad(loss, argnums=range(5))}


def test_gated_delta_frame_lowers_for_tpu_inside_shard_map():
    """The four kernels' outputs declare how they vary over the mesh, the
    parameters cast to varying as the trainer casts them."""
    from jax.sharding import Mesh, PartitionSpec as P

    args, fns = _frame_fns(bsz=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def worker(qkvz, conv_kernel, norm_scale, g, beta):
        conv_kernel, norm_scale = (jax.lax.pcast(p, "data", to="varying") for p in (conv_kernel, norm_scale))
        d_qkvz, d_conv, d_scale, d_g, d_beta = fns["grad"](qkvz, conv_kernel, norm_scale, g, beta)
        return d_qkvz, d_conv[None], d_scale[None], d_g, d_beta

    sharded = jax.shard_map(worker, mesh=mesh, in_specs=(P("data"), P(), P(), P("data"), P("data")), out_specs=P("data"))
    text = jax.jit(sharded).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 4


# (query heads, key heads, head, theta or None, rotary_dim) of the four cells' passes under ``attn.rope`` at T = 8192,
# bf16: trinity_psgd16_t8k's and mellum2_psgd16_t8k's 32 + 4 heads of 128 turned whole (two tables), and without
# positions (trinity's full layer: the norm alone, no table); lfm2_psgd16_t8k's 32 + 8 of 64 (two heads a lane
# block, three tables); qwen3next_psgd16_t8k's 16 + 2 of 256 of which the first 64 lanes turn
QK_ROPE_SHAPES = [
    pytest.param(32, 4, 128, 1e4, 128, id="32+4x128-turned"),
    pytest.param(32, 4, 128, None, 0, id="32+4x128-norm-alone"),
    pytest.param(32, 8, 64, 1e6, 64, id="32+8x64-turned"),
    pytest.param(16, 2, 256, 1e7, 64, id="16+2x256-turning-64"),
]
QK_ROPE_NAMES = ["qk_rope", "qk_rope_bwd"]


def _qk_rope_fns(hq, hk, d, theta, rotary_dim, bsz=1, t=8192, dtype=jnp.bfloat16):
    """The pass as kernels whatever traces them, with its tables built as the
    models build them, and the gradient of q, k and both scales."""
    from network_distributed_pytorch_tpu.models.layers import Rope, rope_tables

    args = [jax.ShapeDtypeStruct((bsz, t, h, d), dtype) for h in (hq, hk)] + [jax.ShapeDtypeStruct((d,), jnp.float32)] * 2

    def forward(q, k, q_scale, k_scale):
        cos, sin = (None, None) if theta is None else rope_tables(Rope(theta), t, rotary_dim)
        return qk_rope.normed_and_turned(q, k, q_scale, k_scale, cos, sin, 1e-6, dtype, interpret=False)

    loss = lambda *a: sum(jnp.sum(jnp.sin(y.astype(jnp.float32))) for y in forward(*a))
    return args, {"forward": forward, "grad": jax.grad(loss, argnums=range(4))}


@pytest.mark.parametrize("hq,hk,d,theta,rotary_dim", QK_ROPE_SHAPES)
def test_qk_rope_lowers_for_tpu_inside_shard_map(hq, hk, d, theta, rotary_dim):
    """Both kernels' outputs declare how they vary over the mesh, the scales
    cast to varying as the trainer casts parameters."""
    from jax.sharding import Mesh, PartitionSpec as P

    args, fns = _qk_rope_fns(hq, hk, d, theta, rotary_dim, bsz=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def worker(q, k, q_scale, k_scale):
        q_scale, k_scale = (jax.lax.pcast(p, "data", to="varying") for p in (q_scale, k_scale))
        dq, dk, d_q_scale, d_k_scale = fns["grad"](q, k, q_scale, k_scale)
        return dq, dk, d_q_scale[None], d_k_scale[None]

    sharded = jax.shard_map(worker, mesh=mesh, in_specs=(P("data"), P("data"), P(), P()), out_specs=P("data"))
    text = jax.jit(sharded).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2


# --- libtpu's Mosaic compiler, without a chip -------------------------------


@pytest.fixture(scope="module")
def v5e_devices():
    """Four AOT-only v5e devices (a 2x2 host), or skip where the installed
    libtpu cannot describe a topology without hardware."""
    from jax.experimental import topologies

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to gate
        pytest.skip(f"no AOT TPU topology on this host: {type(e).__name__}: {e}")
    # a CPU host can write an AOT TPU executable to the persistent cache but
    # not read it back ("DeserializeLoadedExecutable not implemented"), so
    # every run would warn, compile anyway and write again: keep them out
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    yield list(topology.devices)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def _on(device, struct):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(
        struct.shape, struct.dtype, sharding=SingleDeviceSharding(device)
    )


@pytest.mark.parametrize("shape,dtype,causal,masked", FLASH_CASES)
def test_flash_attention_compiles_with_mosaic(v5e_devices, shape, dtype, causal, masked):
    args, fns = _flash_fns(shape, dtype, causal, masked)
    args = [_on(v5e_devices[0], a) for a in args]
    for fn in fns.values():
        jax.jit(fn).lower(*args).compile()


# the benchmark's two per-chip attention shapes (imdb_psgd16_b16 and _x4:
# 48x512; imdb_psgd16_b128: 128x512; both padded) and GPT-2-small's causal one
FLASH_BWD_CASES = [
    pytest.param((48, 512, 12, 64), False, True, id="imdb-48x512"),
    pytest.param((128, 512, 12, 64), False, True, id="imdb-128x512"),
    pytest.param((8, 1024, 12, 64), True, False, id="gpt-1024-causal"),
]


@pytest.mark.parametrize("shape,causal,masked", FLASH_BWD_CASES)
def test_flash_backward_is_a_kernel_in_the_compiled_program(
    v5e_devices, record_property, shape, causal, masked
):
    """The counter that says the mechanism engaged is structural: in the
    program XLA compiled for the chip the whole attention backward is one
    ``tpu_custom_call`` with a name of its own (``breakdown`` rows are named
    after it), beside the forward's; no ``while`` is left of the K-block
    scan it replaced, and no array is larger than q — the scan's
    (B*H, T, block_k) score tensors are what the kernel keeps in VMEM."""

    b, t, h, d = shape
    args, fns = _flash_fns(shape, jnp.bfloat16, causal, masked)
    args = [_on(v5e_devices[0], a) for a in args]
    hlo = jax.jit(fns["grad"]).lower(*args).compile().as_text()
    kernels = re.findall(
        r"(%[\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo
    )
    record_property("tpu_custom_calls", " ".join(kernels))
    assert len(kernels) == 2, kernels
    assert sum(k.startswith("%flash_attention_bwd") for k in kernels) == 1, kernels
    assert not re.search(r"\bwhile\(", hlo)
    sizes = {
        dims: int(np.prod([int(n) for n in dims.split(",")]))
        for dims in re.findall(r"\b(?:bf16|f32|s32|pred)\[([\d,]+)\]", hlo)
    }
    too_large = {dims for dims, n in sizes.items() if n > b * h * t * d}
    assert not too_large, too_large


@pytest.mark.parametrize("shape", GROUPED_MATMUL_SHAPES, ids=str)
def test_grouped_matmul_compiles_with_mosaic_under_its_three_names(v5e_devices, shape):
    """Forward and both cotangents at the expert layers' real shapes: what
    Mosaic would refuse on the chip (a tile's VMEM, a 1856-wide block, a
    select in bf16, the transposed products) it refuses here; each
    ``pallas_call`` is in the compiled program under its own name."""
    args, fns = _grouped_matmul_fns(shape)
    args = [_on(v5e_devices[0], a) for a in args]
    jax.jit(fns["forward"]).lower(*args).compile()
    hlo = jax.jit(fns["grad"]).lower(*args).compile().as_text()
    assert _bare(_custom_calls(hlo)) == ["grouped_matmul", "grouped_matmul_nt", "grouped_matmul_tn"]


@pytest.mark.parametrize("shape", ROWS_TO_TOKENS_SHAPES, ids=str)
def test_tokens_from_rows_compiles_with_mosaic_under_its_name(v5e_devices, shape):
    """At the expert layers' real shapes, fp32 rows forward and a bf16
    cotangent backward: what Mosaic would refuse on the chip (the tokens of
    24,576 rows in SMEM, a copy that starts inside a tile of the rows'
    layout, a row read alone from a packed dtype, the tile's VMEM) it
    refuses here; the call is in the compiled program under its name, and no
    scatter is."""
    args, fns = _rows_to_tokens_fns(shape)
    args = [_on(v5e_devices[0], a) for a in args]
    assert _custom_calls(jax.jit(fns["forward"]).lower(*args).compile().as_text()) == ["tokens_from_rows"]
    hlo = jax.jit(fns["grad"]).lower(*args).compile().as_text()
    assert _bare(_custom_calls(hlo)) == ["tokens_from_rows"] * 2  # the gather's cotangent is the second call
    assert " scatter(" not in hlo and " gather(" in hlo


def test_gated_delta_chunk_local_compiles_with_mosaic_under_its_two_names(v5e_devices, monkeypatch):
    """At the cell's shapes: what Mosaic would refuse on the chip (the lane
    concatenations of the inverse's state, the fp32 products at full
    precision, the transposed bf16 ones, a step's VMEM) it refuses here. And
    the whole rule on a chip takes the kernels: in its compiled gradient each
    is a ``tpu_custom_call`` under its own name, and none of the stage's
    (chunks, heads, 64, 64) fp32 matrices is an array of the program."""
    args, fns, rule_args, rule_grad = _chunk_local_fns()
    on_chip = lambda structs: [_on(v5e_devices[0], a) for a in structs]
    jax.jit(fns["forward"]).lower(*on_chip(args)).compile()
    jax.jit(fns["grad"]).lower(*on_chip(args)).compile()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the backend's own choice, as on the chip
    hlo = jax.jit(rule_grad).lower(*on_chip(rule_args)).compile().as_text()
    kernels = re.findall(r"%([a-z_]+)[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert sorted(kernels) == ["gated_delta_chunk_local", "gated_delta_chunk_local_bwd"], kernels
    assert not re.search(r"f32\[1,128,16,2,64,64\]", hlo)  # A, its powers, the decay, Q K^T: none is an array


def _custom_calls(hlo):
    return sorted(re.findall(r"%([a-z_]+)[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo))


def _bare(kernels):
    """jax wraps a kernel's name in what differentiated it: jvp_grouped_matmul_, transpose_jvp_grouped_matmul_nt__"""
    return sorted(re.sub(r"^(transpose_|jvp_)+|_+$", "", kernel) for kernel in kernels)


FRAME_NAMES = ["gdn_frame_in", "gdn_frame_in_bwd", "gdn_frame_out", "gdn_frame_out_bwd"]


# the cell's own shape; a T that ends inside a tile and inside a HALO block; fp32 at a T under one tile
@pytest.mark.parametrize(
    "t,dtype", [(8192, jnp.bfloat16), (8200, jnp.bfloat16), (1000, jnp.float32)], ids=["cell-8192-bf16", "ragged-8200-bf16", "1000-fp32"]
)
def test_gated_delta_frame_compiles_with_mosaic_under_its_four_names(v5e_devices, t, dtype):
    """What Mosaic would refuse on the chip (the grouped lanes through the
    index maps, the sublane rotations, the HALO blocks, the resident sums, a
    step's VMEM) it refuses here; each ``pallas_call`` is in the compiled
    program under its own name."""
    args, fns = _frame_fns(t=t, dtype=dtype)
    args = [_on(v5e_devices[0], a) for a in args]
    assert _custom_calls(jax.jit(fns["forward"]).lower(*args).compile().as_text()) == FRAME_NAMES[::2]
    assert _custom_calls(jax.jit(fns["grad"]).lower(*args).compile().as_text()) == FRAME_NAMES


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_the_mixer_on_a_chip_takes_the_frames_kernels_once_a_pass(v5e_devices, monkeypatch, remat):
    """The engagement check: one Gated-DeltaNet layer at the cell's shapes,
    its value and gradient compiled for v5e with the backend's own choice, as
    on the chip. The four calls are in the program once a layer-pass each
    (forward, its recomputation where the block is rematerialised, backward),
    and no copy that converts an array the size of q or larger is left under
    ``gdn.frame`` (beta's and g's (T, 32) fp32 relayouts for the rule stay)."""
    from network_distributed_pytorch_tpu.models.qwen3_next import GatedDeltaNet, Qwen3NextConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Qwen3NextConfig(dtype=jnp.bfloat16)
    layer = GatedDeltaNet(cfg, 0.02)
    u = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, cfg.hidden_size))))["params"]
    apply = lambda p, u: layer.apply({"params": p}, u)
    apply = jax.checkpoint(apply) if remat else apply
    loss = lambda p, u: jnp.sum(jnp.sin(apply(p, u).astype(jnp.float32)))
    on_chip = lambda tree: jax.tree_util.tree_map(lambda x: _on(v5e_devices[0], x), tree)
    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(on_chip(params), on_chip(u)).compile().as_text()
    forwards = 2 if remat else 1
    assert _custom_calls(hlo) == sorted(
        ["gated_delta_chunk_local"] * forwards + ["gated_delta_chunk_local_bwd"]
        + FRAME_NAMES[::2] * forwards + FRAME_NAMES[1::2]
    )
    q_size = 8192 * cfg.linear_key_heads * cfg.linear_key_head_dim
    for line in hlo.splitlines():
        if " copy(" in line and "gdn.frame" in line and "convert_element_type" in line:
            dims = re.search(r"= \w+\[([\d,]+)\]", line).group(1)
            assert np.prod([int(n) for n in dims.split(",")]) < q_size, line
    assert "gdn.conv" not in hlo  # the conv sits inside the ``in`` pass


@pytest.mark.parametrize("hq,hk,d,theta,rotary_dim", QK_ROPE_SHAPES)
def test_qk_rope_compiles_with_mosaic_under_its_two_names(v5e_devices, hq, hk, d, theta, rotary_dim):
    """What Mosaic would refuse on the chip (the lane rotations, the masked
    sums over a lane block's two heads, a lane block cut out of a head of 256,
    the resident sums, a step's VMEM) it refuses here; each ``pallas_call`` is
    in the compiled program under its own name."""
    args, fns = _qk_rope_fns(hq, hk, d, theta, rotary_dim)
    args = [_on(v5e_devices[0], a) for a in args]
    assert _custom_calls(jax.jit(fns["forward"]).lower(*args).compile().as_text()) == QK_ROPE_NAMES[:1]
    assert _custom_calls(jax.jit(fns["grad"]).lower(*args).compile().as_text()) == QK_ROPE_NAMES


def _attention_layers():
    from network_distributed_pytorch_tpu.models import afmoe, lfm2, mellum, qwen3_next
    from network_distributed_pytorch_tpu.models.layers import FULL

    bf16 = dict(dtype=jnp.bfloat16)
    yield "trinity-sliding-kept", afmoe.AfmoeAttention(afmoe.AfmoeConfig(**bf16), True, 0.02), False
    yield "trinity-sliding-recomputed", afmoe.AfmoeAttention(afmoe.AfmoeConfig(**bf16), True, 0.02), True
    yield "trinity-full-recomputed", afmoe.AfmoeAttention(afmoe.AfmoeConfig(**bf16), False, 0.02), True
    yield "mellum2-full-yarn-recomputed", mellum.MellumAttention(mellum.MellumConfig(**bf16), FULL, 0.02), True
    yield "lfm2-recomputed", lfm2.Lfm2Attention(lfm2.Lfm2Config(**bf16), 0.02), True
    yield "qwen3next-recomputed", qwen3_next.GatedAttention(qwen3_next.Qwen3NextConfig(**bf16), 0.02), True


@pytest.mark.parametrize("layer,remat", [pytest.param(layer, remat, id=name) for name, layer, remat in _attention_layers()])
def test_an_attention_layer_on_a_chip_takes_the_rope_kernels_once_a_pass(v5e_devices, monkeypatch, layer, remat):
    """The engagement check: one attention layer of each model at its
    published widths and T = 8192, its value and gradient compiled for v5e
    with the backend's own choice, as on the chip. The forward kernel is in the
    program once a pass (forward, its recomputation where the block is
    rematerialised), the backward once, beside the flash kernels; and no
    fusion under ``attn.rope`` reads or writes an array larger than k: what
    XLA still fuses there is the tables, (T, 128) fp32, and the scales. (Plain
    copies stay under the scope's reshapes where the kernels' flat layout is
    not its neighbour's: lfm2's fold of its heads of 64, qwen3next's q cut
    out of ``q_proj``'s [q | gate] columns.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = layer.config
    u = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, cfg.hidden_size))))["params"]
    apply = lambda p, u: layer.apply({"params": p}, u)
    apply = jax.checkpoint(apply) if remat else apply
    loss = lambda p, u: jnp.sum(jnp.sin(apply(p, u).astype(jnp.float32)))
    on_chip = lambda tree: jax.tree_util.tree_map(lambda x: _on(v5e_devices[0], x), tree)
    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(on_chip(params), on_chip(u)).compile().as_text()
    forwards = 2 if remat else 1
    assert _custom_calls(hlo) == sorted(["flash_attention", "qk_rope"] * forwards + ["flash_attention_bwd", "qk_rope_bwd"])
    k_size = 8192 * cfg.n_kv_heads * cfg.head_dim
    instruction = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)(?:, |$)")
    elements = lambda types: [int(np.prod([int(n) for n in dims.split(",") if n])) for dims in re.findall(r"\w+\[([\d,]*)\]", types)]
    parsed = [m.groups() + (line,) for line in hlo.splitlines() if (m := instruction.match(line))]
    result_of = {name: types for name, types, _, _, _ in parsed}
    under = [row for row in parsed if "attn.rope" in row[4] and row[2] == "fusion"]  # none where no table is built: trinity's full layer
    for name, types, _, operands, line in under:
        touched = elements(types) + [n for operand in re.findall(r"%([\w.\-]+)", operands) for n in elements(result_of.get(operand, ""))]
        assert max(touched, default=0) <= k_size, line[:300]


def test_the_sdar_attention_layer_on_a_chip_walks_the_rule_inside_the_flash_kernels(v5e_devices, monkeypatch):
    """SDAR's attention layer at its published widths over the cell's 16,384
    rows (a noised copy of 8,192 tokens beside the clean one), rematerialised,
    value and gradient compiled for v5e with the backend's own choice: the
    block-wise rule is the flash kernels' loop bounds (forward, recomputed,
    backward: K, V and eight operands of 16,384 rows whole in VMEM), the
    positions 0..8191 twice reach ``qk_rope`` as its tables, XLA is left no
    ``while`` and no score-sized array, and under ``attn.blockwise`` it
    multiplies nothing: the three kernels, and the sums of a key/value group's
    dK and dV."""
    from network_distributed_pytorch_tpu.models.sdar import SdarAttention, SdarConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = SdarAttention(SdarConfig(dtype=jnp.bfloat16), 0.02)
    rows, cfg = 16384, layer.config
    u = jax.ShapeDtypeStruct((1, rows, cfg.hidden_size), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, cfg.hidden_size))))["params"]
    apply = jax.checkpoint(lambda p, u: layer.apply({"params": p}, u))
    loss = lambda p, u: jnp.sum(jnp.sin(apply(p, u).astype(jnp.float32)))
    on_chip = lambda tree: jax.tree_util.tree_map(lambda x: _on(v5e_devices[0], x), tree)
    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(on_chip(params), on_chip(u)).compile().as_text()
    assert _custom_calls(hlo) == sorted(["flash_attention", "qk_rope"] * 2 + ["flash_attention_bwd", "qk_rope_bwd"])
    assert not re.search(r"\bwhile\(", hlo)
    sizes = [int(np.prod([int(n) for n in dims.split(",")])) for dims in re.findall(r"\b(?:bf16|f32|s32|pred)\[([\d,]+)\]", hlo)]
    assert max(sizes) <= rows * cfg.n_heads * cfg.head_dim  # q's size: no (rows, rows) mask or score reaches HBM
    under = [line for line in hlo.splitlines() if "attn.blockwise" in line and " = " in line]
    assert sum("custom-call(" in line for line in under) == 3
    assert not any(re.search(r" (while|dot|convolution)\(", line) for line in under)  # XLA multiplies nothing there


@pytest.mark.parametrize("shape", ORTHOGONALIZE_SHAPES, ids=str)
def test_pallas_orthogonalize_compiles_with_mosaic(v5e_devices, shape):
    arg = _on(v5e_devices[0], jax.ShapeDtypeStruct(shape, jnp.float32))
    jax.jit(orthogonalize_pallas).lower(arg).compile()


def test_smoke_train_step_compiles_for_four_v5e_chips(v5e_devices, monkeypatch):
    """chip_smoke's step — DistilBERT-base widths (one layer: the widths
    decide what Mosaic sees, the depth only repeats it), PowerSGD rank 16,
    bf16, 16 sequences of 256 per chip — built as on the chip ("auto"
    resolving to flash and the Pallas Gram-Schmidt, compiled, inside
    ``shard_map``) and compiled for a four-chip v5e host. The compiled
    all-reduce bytes must equal the wire ledger."""
    from network_distributed_pytorch_tpu.models.distilbert import (
        DistilBertConfig,
        DistilBertForSequenceClassification,
    )
    from network_distributed_pytorch_tpu.parallel import PowerSGDReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import make_train_step
    from network_distributed_pytorch_tpu.utils.hlo_audit import hlo_text_of_compiled
    from network_distributed_pytorch_tpu.utils.losses import cross_entropy_loss

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq_len, per_chip = 256, 16
    mesh = make_mesh(devices=v5e_devices)
    model = DistilBertForSequenceClassification(
        DistilBertConfig(n_layers=1, dtype=jnp.bfloat16)
    )
    params = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, seq_len), jnp.int32),
            jnp.ones((1, seq_len), jnp.int32),
        )
    )["params"]

    def loss_fn(params, model_state, batch):
        logits = model.apply(
            {"params": params}, batch["input_ids"], batch["attention_mask"],
            deterministic=True,
        )
        return cross_entropy_loss(logits, batch["labels"]), model_state

    reducer = PowerSGDReducer(compression_rank=16, matricize="last")
    assert (reducer.orthogonalize_impl, reducer._interpret) == ("pallas", False)
    step = make_train_step(
        loss_fn, reducer, params, learning_rate=5e-5, algorithm="ef_momentum",
        mesh=mesh,
    )
    batch = per_chip * mesh.size
    compiled = step.fn.lower(
        jax.eval_shape(step.init_state, params),
        {
            "input_ids": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32),
            "attention_mask": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch,), jnp.int32),
        },
    ).compile()
    hlo = hlo_text_of_compiled(compiled)
    assert "tpu_custom_call" in hlo  # the kernels are in the program
    audit = step.ledger.reconcile(hlo)
    assert audit["exact"] and audit["hlo_collective_count"] > 0, audit


def _selective_scan_fns(bsz=1, t=8192, ch=5120, n=16):
    """phi4flash_psgd16_t8k's one scan: a sequence of 8192, 5120 channels, a state of 16; the kernels whatever traces them."""
    from network_distributed_pytorch_tpu.ops.selective_scan import selective_scan

    f32, bf16 = jnp.float32, jnp.bfloat16
    struct = jax.ShapeDtypeStruct
    args = (
        struct((bsz, t, ch), bf16), struct((bsz, t, ch), f32), struct((ch, n), f32),
        struct((bsz, t, n), bf16), struct((bsz, t, n), bf16), struct((ch,), f32),
    )
    scan = lambda *v: selective_scan(*v, interpret=False)
    loss = lambda *v: scan(*v).astype(f32).sum()
    return args, {"forward": scan, "grad": jax.grad(loss, argnums=tuple(range(6)))}


@pytest.mark.parametrize("which,kernels", [("forward", ["selective_scan"]), ("grad", ["selective_scan", "selective_scan_bwd"])])
def test_selective_scan_compiles_for_v5e_and_never_holds_the_states_of_a_sequence(v5e_devices, which, kernels):
    """At the cell's shape the (T, C, N) states of a sequence are 2.7 GB in
    fp32: the two kernels are in the compiled program under their names, and
    its temporaries stay under a third of that, forward and with every
    cotangent (AOT for v5e read 0.021 GB forward, the (64, 16, 5120) states
    the time blocks start from, and nothing beside the results with every
    cotangent; the plain walk's program read 0.21 and 0.51). What Mosaic would
    refuse on the chip (a row read alone, a gather along the lanes, the sums down the
    sublanes and over the lanes, a step's VMEM) it refuses here, and what each
    kernel asks of VMEM is under what any kernel of this file may."""
    from network_distributed_pytorch_tpu.ops.flash_attention import _VMEM_MOST

    args, fns = _selective_scan_fns()
    lowered = jax.jit(fns[which]).lower(*[_on(v5e_devices[0], a) for a in args])
    asks = re.findall(r'\\22size\\22: (\d+)}]}", kernel_name = "(\w+)"', lowered.as_text())
    assert sorted(name for _, name in asks) == kernels and all(int(ask) <= _VMEM_MOST for ask, _ in asks), asks
    compiled = lowered.compile()
    assert _bare(_custom_calls(compiled.as_text())) == kernels
    assert compiled.memory_analysis().temp_size_in_bytes < 8192 * 5120 * 16 * 4 / 3


def test_the_phi4flash_cuts_step_lowers_for_tpu(monkeypatch):
    """The benchmark's cut (layers 15-19 at the published widths, T = 8192,
    bf16, ``remat``) through ``make_train_step`` under PowerSGD rank 16,
    cross-lowered for TPU as the chip builds it ("auto" resolving to the flash
    kernels and the Pallas Gram-Schmidt): the three attention layers' kernels
    are in the program, forward, recomputed and backward, and the scan's two:
    ``selective_scan`` forward and recomputed, ``selective_scan_bwd`` once, and
    no ``while`` is left in the step. What says that each softmax is made once
    is the kernels' own operands: 40 query rows of 64 lanes a sequence (not
    80) over 20 key rows of 64 and 20 value rows of 128, in every forward and
    every backward. (The whole compile for v5e is
    ``benchmark/tests/test_aot_v5e.py``'s.)"""
    from network_distributed_pytorch_tpu.models.layers import next_token_lm_loss, zero_counters
    from network_distributed_pytorch_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashLM
    from network_distributed_pytorch_tpu.parallel import PowerSGDReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS, make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq_len = 8192
    model = Phi4FlashLM(Phi4FlashConfig(
        vocab_size=25008, layer_indices=(15, 16, 17, 18, 19), dtype=jnp.bfloat16, remat=True,
    ))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32)))["params"]
    step = make_train_step(
        next_token_lm_loss(model), PowerSGDReducer(compression_rank=16, matricize="last"), params,
        learning_rate=5e-5, algorithm="ef_momentum", mesh=make_mesh(devices=jax.devices()[:1]),
    )
    state = jax.eval_shape(lambda p: step.init_state(p, model_state={STEP_COUNTERS: zero_counters(model.config)}), params)
    tokens = jax.ShapeDtypeStruct((1, seq_len), jnp.int32)
    text = step.fn.trace(state, {"input_ids": tokens, "labels": tokens}).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 12  # three layers' flash forward, recomputed forward and backward, and the scan's
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert (names.count("selective_scan"), names.count("selective_scan_bwd")) == (2, 1)
    assert "stablehlo.while" not in text  # the scan's loops are inside the kernels
    operands = re.findall(r'kernel_name = "(_flash_kernel|flash_attention_bwd)"[^\n]*? : \(([^)]*)\) ->', text)
    assert {name for name, _ in operands} == {"_flash_kernel", "flash_attention_bwd"}
    qkv = "tensor<40x8192x64xbf16>, tensor<20x8192x64xbf16>, tensor<20x8192x128xbf16>, "
    assert all(types.startswith(qkv) for _, types in operands), operands


def test_the_sdar_cuts_step_lowers_for_tpu(monkeypatch):
    """The benchmark's cut (four layers at the published widths, 16 of 128
    experts, 8,192 tokens as 16,384 rows, bf16, ``remat``) through
    ``make_train_step`` under PowerSGD rank 16 with the masked-token loss,
    cross-lowered for TPU as the chip builds it: every layer's flash kernels
    are in the program, forward, recomputed and backward, over 16,384 rows of
    32 query heads and 4 key/value heads of 128 read in place. (The whole
    compile for v5e is ``benchmark/tests/test_aot_v5e.py``'s.)"""
    from network_distributed_pytorch_tpu.models.layers import masked_token_loss, zero_counters
    from network_distributed_pytorch_tpu.models.sdar import SdarConfig, SdarLM
    from network_distributed_pytorch_tpu.parallel import PowerSGDReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import STEP_COUNTERS, make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    length = 8192
    model = SdarLM(SdarConfig(
        vocab_size=18992, n_layers=4, held_experts=tuple(range(16)), dtype=jnp.bfloat16, remat=True,
    ))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]
    assert sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)) == 456_346_624
    step = make_train_step(
        masked_token_loss(model), PowerSGDReducer(compression_rank=16, matricize="last"), params,
        learning_rate=5e-5, algorithm="ef_momentum", mesh=make_mesh(devices=jax.devices()[:1]),
    )
    counters = zero_counters(model.config, masked_token_loss.counters)
    state = jax.eval_shape(lambda p: step.init_state(p, model_state={STEP_COUNTERS: counters}), params)
    tokens = jax.ShapeDtypeStruct((1, length), jnp.int32)
    batch = {"input_ids": tokens, "noisy_ids": tokens, "loss_weight": jax.ShapeDtypeStruct((1, length), jnp.float32)}
    text = step.fn.trace(state, batch).lower(lowering_platforms=("tpu",)).as_text()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    # four alike layers share one lowered block: its forward, its recomputation and its backward
    assert (names.count("_flash_kernel"), names.count("flash_attention_bwd")) == (2, 1)
    assert (names.count("qk_rope"), names.count("qk_rope_bwd")) == (2, 1)
    operands = re.findall(r'kernel_name = "(_flash_kernel|flash_attention_bwd)"[^\n]*? : \(([^)]*)\) ->', text)
    qkv = "tensor<1x16384x4096xbf16>, tensor<1x16384x512xbf16>, tensor<1x16384x512xbf16>, "
    assert all(types.startswith(qkv) for _, types in operands), operands


def test_flash_grad_without_a_mask_types_inside_shard_map(v5e_devices):
    """GPT's causal attention passes no mask. The kernel then makes its own
    — invariant over the mesh, while the backward's dmask varies as the
    data does, and a ``custom_vjp`` cotangent must have its primal's type
    (``gpt_lm --preset full`` died of this at trace time on the chip, PR 21;
    DistilBERT's mask comes from the batch and never showed it)."""
    from jax.sharding import PartitionSpec as P

    from network_distributed_pytorch_tpu.parallel import make_mesh

    mesh = make_mesh(devices=v5e_devices)

    def local_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jax.lax.pmean(out.astype(jnp.float32).sum(), "data")

    grad = jax.jit(jax.shard_map(
        jax.grad(local_loss, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(P("data"),) * 3, out_specs=(P("data"),) * 3,
    ))
    qkv = [jax.ShapeDtypeStruct((8, 64, 12, 64), jnp.bfloat16)] * 3
    grad.lower(*qkv).compile()


# --- the reducer path the trainer takes on TPU, stepped on the CPU mesh -----


def test_pallas_orthogonalize_reducer_steps_inside_shard_map(devices):
    """``orthogonalize_impl="pallas"`` (what "auto" resolves to on TPU) must
    trace inside the trainer's ``shard_map`` — its ``pallas_call`` has to
    declare how the output varies over the mesh — and agree with the XLA
    Gram-Schmidt."""
    from network_distributed_pytorch_tpu.parallel import PowerSGDReducer, make_mesh
    from network_distributed_pytorch_tpu.parallel.trainer import make_train_step

    mesh = make_mesh(devices=devices[:4])
    key = jax.random.PRNGKey(0)
    params = {
        "w1": jax.random.normal(key, (24, 16)) * 0.1,
        "w2": jax.random.normal(jax.random.fold_in(key, 1), (16, 4)) * 0.1,
        "b": jnp.zeros((4,)),
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (16, 24))
    y = jax.random.normal(jax.random.fold_in(key, 3), (16, 4))

    def loss_fn(p, model_state, batch):
        bx, by = batch
        pred = jnp.tanh(bx @ p["w1"]) @ p["w2"] + p["b"]
        return jnp.mean((pred - by) ** 2), model_state

    losses = {}
    for impl in ("pallas", "xla"):
        reducer = PowerSGDReducer(
            compression_rank=2, matricize="last", orthogonalize_impl=impl
        )
        step = make_train_step(
            loss_fn, reducer, params, learning_rate=0.05,
            algorithm="ef_momentum", mesh=mesh, donate_state=False,
        )
        state = step.init_state(params)
        for _ in range(3):
            state, loss = step(state, (x, y))
        losses[impl] = float(loss)
    assert np.isfinite(losses["pallas"])
    np.testing.assert_allclose(losses["pallas"], losses["xla"], rtol=1e-5)


# the distinct P factors of the benchmark's cells that no case above has at
# its own shape: DistilBERT's three at rank 16 (31, 6 and 1 matrices), the
# Nemotron mixer's in_proj, and ResNet-152's largest rank-4 group (36 convs)
CELL_P_SHAPES = [(768, 16), (3072, 16), (30522, 16), (21504, 16), (2304, 4)]


@pytest.mark.parametrize("n,r", CELL_P_SHAPES)
def test_gram_schmidt_lowers_at_cell_shapes(monkeypatch, n, r):
    """What the reducer runs on the chip, at the shapes it runs it at: built
    as there ("auto" resolving to the compiled Pallas Gram-Schmidt), its
    orthogonalisation of an (n, r) factor lowers for TPU."""
    from network_distributed_pytorch_tpu.parallel import PowerSGDReducer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    reducer = PowerSGDReducer(compression_rank=r, matricize="last")
    assert (reducer.orthogonalize_impl, reducer._interpret) == ("pallas", False)
    lowered = jax.jit(reducer._orthogonalize).trace(
        jax.ShapeDtypeStruct((n, r), jnp.float32)
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
