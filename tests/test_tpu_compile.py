"""Every Pallas kernel compiles for a described TPU v5e.

The interpret-mode tests (tests/test_kernels.py) run the kernel bodies
on the CPU and never see the TPU lowering's block-shape, layout and
VMEM rules. These tests hand the TPU compiler installed with JAX a v5e
chip that is described, not attached, and compile each kernel at the
head shapes of the registered configurations the engine serves:
smollm-135m (H=9, Hk=3, D=64) for all five, minitron-4b (H=24, Hk=8,
D=128) for the two paged kernels, h2o-danube-1.8b (H=32, Hk=8, D=80) for
the batched decode kernel, mamba2-2.7b for the SSD scan. The batched
decode kernel is also compiled at each benchmark cell's own slots and
pool, where its double-buffered pages must fit the scoped VMEM. Nothing
runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture: only one
process at a time may load the TPU library, so describing it while the
module is imported would break collection under several test workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    batched_paged_decode_attention,
    chunked_prefill_attention,
    flash_attention,
    paged_decode_attention,
    ssd_scan,
)

# (name, H, Hk, D) of the configurations whose head shapes are compiled
SMOLLM = ("smollm-135m", 9, 3, 64)
MINITRON = ("minitron-4b", 24, 8, 128)
DANUBE = ("h2o-danube-1.8b", 32, 8, 80)

# engine-sized pool: 16 slots x 1024 tokens in 16-token pages
N_SLOTS, PAGE, PAGES_PER_SEQ = 16, 16, 64
N_POOL_PAGES = N_SLOTS * PAGES_PER_SEQ + 65


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, kernel=None):
    """Compile ``fn`` for the described chip; return its HLO text. With
    ``kernel``, every Pallas call in it must carry that name (the
    profiler names a kernel's operation by it)."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    if kernel is not None:
        calls = [line.strip() for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert all(c.startswith(f"%{kernel}") or
                   c.startswith(f"ROOT %{kernel}") for c in calls), calls
    return text


def _pool(Hk, D):
    return ((N_POOL_PAGES, Hk, PAGE, D), jnp.bfloat16)


def _table():
    return ((N_SLOTS, PAGES_PER_SEQ), jnp.int32)


@pytest.mark.parametrize("model", [SMOLLM, MINITRON], ids=lambda m: m[0])
def test_paged_decode_compiles(model, one_chip, no_persistent_cache):
    _, H, Hk, D = model
    _compile(lambda q, k, v, pt, sl: paged_decode_attention(q, k, v, pt, sl),
             one_chip,
             ((N_SLOTS, H, D), jnp.bfloat16), _pool(Hk, D), _pool(Hk, D),
             _table(), ((N_SLOTS,), jnp.int32),
             kernel="paged_decode_attention")


def _compile_batched_decode(model, sharding, n_slots, pages_per_seq,
                            n_pool_pages, max_pages):
    _, H, Hk, D = model

    def fn(q, k, v, pt, sl, kn, vn):
        return batched_paged_decode_attention(q, k, v, pt, sl, kn, vn,
                                              max_pages=max_pages)
    pool = ((n_pool_pages, Hk, PAGE, D), jnp.bfloat16)
    _compile(fn, sharding,
             ((n_slots, H, D), jnp.bfloat16), pool, pool,
             ((n_slots, pages_per_seq), jnp.int32), ((n_slots,), jnp.int32),
             ((n_slots, Hk, D), jnp.bfloat16),
             ((n_slots, Hk, D), jnp.bfloat16),
             kernel="batched_paged_decode_attention")


# max_pages 12 leaves a ragged last block of pages at every head shape
@pytest.mark.parametrize("max_pages", [None, 8, 12])
@pytest.mark.parametrize("model", [SMOLLM, MINITRON, DANUBE],
                         ids=lambda m: m[0])
def test_batched_paged_decode_compiles(model, max_pages, one_chip,
                                       no_persistent_cache):
    _compile_batched_decode(model, one_chip, N_SLOTS, PAGES_PER_SEQ,
                            N_POOL_PAGES, max_pages)


# the benchmark cells' engines: slots, pages per sequence, pool pages
# (every slot's table, the prefix cache's pages, the scratch page)
@pytest.mark.parametrize("model,n_slots,pages_per_seq,n_pool_pages", [
    (SMOLLM, 128, 64, 128 * 64 + 1),
    (DANUBE, 64, 64, 64 * 64 + 1),
    (DANUBE, 23, 144, 23 * 144 + 768 + 1),
], ids=["smollm135m-chat", "danube1.8b-chat", "danube1.8b-rag"])
def test_batched_paged_decode_compiles_at_cell_size(
        model, n_slots, pages_per_seq, n_pool_pages, one_chip,
        no_persistent_cache):
    _compile_batched_decode(model, one_chip, n_slots, pages_per_seq,
                            n_pool_pages, pages_per_seq)


# chunk lengths the engine launches: a full budget slab, and a ragged
# final slab (the engine compiles one program per distinct length)
@pytest.mark.parametrize("chunk", [256, 37])
@pytest.mark.parametrize("model", [SMOLLM, MINITRON], ids=lambda m: m[0])
def test_chunked_prefill_compiles(model, chunk, one_chip,
                                  no_persistent_cache):
    _, H, Hk, D = model
    _compile(lambda q, k, v, pt, qo, kl: chunked_prefill_attention(
                 q, k, v, pt, qo, kl),
             one_chip,
             ((1, chunk, H, D), jnp.bfloat16), _pool(Hk, D), _pool(Hk, D),
             ((1, PAGES_PER_SEQ), jnp.int32), ((1,), jnp.int32),
             ((1,), jnp.int32), kernel="chunked_prefill_attention")


@pytest.mark.parametrize("L", [512, 200])
def test_flash_attention_compiles(L, one_chip, no_persistent_cache):
    _, H, Hk, D = SMOLLM
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
             one_chip,
             ((1, L, H, D), jnp.bfloat16), ((1, L, Hk, D), jnp.bfloat16),
             ((1, L, Hk, D), jnp.bfloat16))


def test_ssd_scan_compiles(one_chip, no_persistent_cache):
    # mamba2-2.7b: d_inner 5120 = 80 heads of 64, one B/C group, N=128
    B, L, H, P, G, N, chunk = 1, 512, 80, 64, 1, 128, 256
    _compile(lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=chunk),
             one_chip,
             ((B, L, H, P), jnp.bfloat16), ((B, L, H), jnp.float32),
             ((B, L, G, N), jnp.bfloat16), ((B, L, G, N), jnp.bfloat16))
