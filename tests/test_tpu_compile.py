"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks not aligned
to the (8, 128) tiling, kernels over the scoped VMEM limit, programs that
do not fit HBM.  These tests compile at real widths so such a refusal
shows here, not on the chip.  Nothing runs: they lower from shapes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import
every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import rmsnorm as rn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("block_rows", [128, 256])
@pytest.mark.parametrize("d", [4096, 576])     # chatglm3-6b, smollm-135m
def test_fused_add_rmsnorm_compiles(one_chip, d, block_rows):
    x = jax.ShapeDtypeStruct((4096, d), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((d,), jnp.bfloat16, sharding=one_chip)

    def f(x, y, g):
        return rn.fused_add_rmsnorm(x, y, g, block_rows=block_rows,
                                    interpret=False)

    compiled = jax.jit(f).lower(x, x, g).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's name= names its instruction, which a profile shows
    assert "%add_rmsnorm" in text


def test_smollm_decode_step_compiles(one_chip):
    from repro import api
    program = api.compile("smollm-135m")
    step = program.decode_tiers(4, 2048, tiers=(4,))[4]
    params = jax.eval_shape(lambda: program.init_params(0))
    batch = {k: sds for k, (sds, _) in step.batch_inputs.items()}
    batch.update(program.model.decode_cache_env(4, 2048))
    compiled = jax.jit(lambda p, b: step.fn(p, b)).lower(
        _placed(params, one_chip), _placed(batch, one_chip)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes
    assert 0 < used < 16e9


@pytest.mark.parametrize("name", ["add_rmsnorm", "rmsnorm",
                                  "flash_attention", "grouped_matmul"])
def test_kernel_name_reaches_tpu_lowering(one_chip, name):
    """Decode attention and the SSD scan are left out: the TPU lowering
    refuses them whatever their name (a VMEM scalar store, cumsum)."""
    from repro.kernels import flash_attention, grouped_matmul
    bf = jnp.bfloat16
    fn, shapes = {
        "add_rmsnorm": (lambda x, y, g: rn.fused_add_rmsnorm(
            x, y, g, interpret=False),
            [(256, 576), (256, 576), (576,)]),
        "rmsnorm": (lambda x, g: rn.rmsnorm(x, g, interpret=False),
                    [(256, 576), (576,)]),
        "flash_attention": (
            lambda q, k, v: flash_attention.flash_attention(
                q, k, v, interpret=False), [(1, 256, 4, 128)] * 3),
        "grouped_matmul": (
            lambda x, w1, w3, w2: grouped_matmul.grouped_ffn(
                x, w1, w3, w2, interpret=False),
            [(2, 128, 256), (2, 256, 512), (2, 256, 512), (2, 512, 256)]),
    }[name]
    args = [jax.ShapeDtypeStruct(s, bf, sharding=one_chip) for s in shapes]
    text = jax.jit(fn).lower(*args).as_text()
    assert f'kernel_name = "{name}"' in text


def test_grouped_decode_attention_compiles_without_expansion(one_chip):
    """chatglm3-6b's decode attention (32 q heads over 2 KV heads) at 16
    rows x 2048 positions keeps no copy of K or V per q head: one such
    copy is 256 MiB, the grouped program's scratch a fraction of 1 MiB."""
    from repro.models.layers import DecodeAttentionOp, HeadLayout
    op = DecodeAttentionOp(HeadLayout(32, 2, 1, 128))
    assert op.grouped
    B, S = 16, 2048

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    new = sds((B, 1, 2, 128))
    cache = sds((B, S, 2, 128))
    compiled = jax.jit(lambda *a: op.kernel({}, *a),
                       donate_argnums=(3, 4)).lower(
        sds((B, 1, 32, 128)), new, new, cache, cache,
        sds((B,), jnp.int32)).compile()
    per_q_copy = B * S * 32 * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < per_q_copy / 64
    assert " gather(" not in compiled.as_text()
