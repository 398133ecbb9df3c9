"""Ahead-of-time compiles for a DESCRIBED TPU v5e (no chip attached):
the kernels of the main path at the shapes chip_smoke.py launches, so
that what the chip's compiler would refuse (a misaligned slice, too
much VMEM, a program that cannot be partitioned) fails here, in tier-1,
at no chip time. A compile that passes is not a chip run: results and
times come from ``python chip_smoke.py`` on the chip.

The topology is described inside a module-scoped fixture — never at
import, in a skipif condition or a parametrize argument: only one
process at a time may load the TPU library, and every xdist worker
imports every test file. All cases live in this ONE file so one worker
holds the library.

Tier-1 cases take seconds each. The ed25519 kernels (Pallas ~3 min,
XLA ~30-40 s), the BLS pairing kernel (~2 min) and the large BLS
aggregates are marked ``slow`` and run by hand before a chip call:

    JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_compile.py -m slow
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 topology, with the persistent compile
    cache off while this module runs: a compile for a described chip is
    written to the cache but cannot be read back without one (the next
    run would warn and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here / the library is held
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """(batch-axis sharding, replicated sharding) over the 2x2 mesh —
    the shardings ops/mesh.DeviceMesh builds on a four-chip host."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(topo.devices), ("dp",))
    return (NamedSharding(mesh, PartitionSpec("dp")),
            NamedSharding(mesh, PartitionSpec()))


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def specs_like(arrays, sharding):
    return [spec(a.shape, a.dtype, sharding) for a in arrays]


HBM_BYTES = 16 * 2 ** 30           # one v5e chip
TREE_BYTES = 2 * (1 << 20) * 32    # the resident 2^20-leaf tree, all levels


def compile_for_chip(fn, *args, pallas: bool = False, **static):
    """Lower + compile for the described device; assert it fits HBM
    beside the 2^20-leaf tree, and that a Pallas kernel is really in
    the program where one is expected."""
    compiled = fn.lower(*args, **static).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert need + TREE_BYTES < HBM_BYTES, need
    calls = compiled.as_text().count("tpu_custom_call")
    assert (calls > 0) == pallas, calls
    return compiled


def ed25519_specs(batch, sharding):
    return [spec((batch, 20), jnp.int32, sharding),
            spec((batch,), jnp.int32, sharding),
            spec((batch, 20), jnp.int32, sharding),
            spec((batch,), jnp.int32, sharding),
            spec((batch, 8), jnp.uint32, sharding),
            spec((batch, 8), jnp.uint32, sharding)]


def bls_aggregate_specs(jobs, n, sharding):
    from plenum_tpu.ops import bls381_jax as bj
    raw = np.zeros((jobs * n, 48), dtype=np.uint8)
    raw[:, 0] = 0xC0
    limbs, sign_big, is_inf, valid = bj.pack_compressed(raw)
    return specs_like((limbs.reshape(jobs, n, bj.NLIMB),
                       sign_big.reshape(jobs, n), is_inf.reshape(jobs, n),
                       valid.reshape(jobs, n)), sharding)


N_LEAVES = 1 << 20


# ------------------------------------------------------------- tier-1

@pytest.mark.parametrize("nblocks", [1, 2])
def test_sha256_pallas_kernel(one_chip, nblocks):
    """The compiled (not interpreted) compression kernel: leaf hashing
    (1 block) and RFC 6962 node hashing (2 blocks)."""
    from plenum_tpu.ops import sha256_pallas as sp
    compile_for_chip(sp._build_sha256(4, nblocks),
                     spec((4 * sp.BLOCK, nblocks, 16), jnp.uint32, one_chip),
                     spec((4 * sp.BLOCK,), jnp.int32, one_chip), pallas=True)


def test_pallas_cache_key_ignores_the_callers_stack(one_chip):
    """The Mosaic payload of a Pallas kernel embeds its ops' source
    locations where the cache-key canonicalisation cannot strip them.
    With full tracebacks (JAX's default) those locations carry the
    CALLER's frames, so the verify daemon, a node and chip_smoke.py
    each got their own persistent-cache key — and their own ~4 min
    compile — for the same ed25519 kernel.
    ops.enable_persistent_compilation_cache (conftest calls it) keeps
    the innermost frame only: the same kernel lowered from two
    different call stacks is the same program text."""
    from plenum_tpu.ops import sha256_pallas as sp
    assert not jax.config.jax_include_full_tracebacks_in_locations
    args = (spec((sp.BLOCK, 1, 16), jnp.uint32, one_chip),
            spec((sp.BLOCK,), jnp.int32, one_chip))

    def lowered_text():
        sp._build_sha256.cache_clear()
        ir = sp._build_sha256(1, 1).lower(*args).compiler_ir()
        return ir.operation.get_asm(enable_debug_info=False)

    def from_a_deeper_stack():
        return (lambda: lowered_text())()

    text = lowered_text()
    assert "tpu_custom_call" in text
    assert from_a_deeper_stack() == text


def test_merkle_fused_build_2_20_leaves(one_chip):
    """ONE jit: leaf SHA-256 + every interior level of the 2^20-leaf
    ledger (BASELINE.json config 4), Pallas on every level that fills a
    kernel block."""
    from plenum_tpu.ops import merkle
    compiled = compile_for_chip(
        merkle._build_levels,
        spec((N_LEAVES, 1, 16), jnp.uint32, one_chip),
        spec((N_LEAVES,), jnp.int32, one_chip),
        nblocks=1, depth=20, backend="pallas", pallas=True)
    # levels of 2^20 .. 2^10 rows each take the kernel
    assert compiled.as_text().count("tpu_custom_call") == 11


def test_merkle_fused_level_append(one_chip):
    """The fused multi-level append jit at the 8,192-leaf append onto a
    grown 2^20-leaf tree: four levels per dispatch, Pallas from the
    level that fills a block."""
    from plenum_tpu.ops import merkle
    cap = 2 * N_LEAVES

    def level(h):
        return spec((cap >> h, 8), jnp.uint32, one_chip)

    compile_for_chip(
        merkle._append_levels_fused, level(0),
        tuple(level(h) for h in (1, 2, 3, 4)),
        spec((4,), jnp.int32, one_chip), spec((4,), jnp.int32, one_chip),
        buckets=(4096, 2048, 1024, 512), backend="pallas", pallas=True)


def test_merkle_audit_path_gather(one_chip):
    """4,096 proofs against the two device-resident bottom levels."""
    from plenum_tpu.ops import merkle
    compile_for_chip(
        merkle._gather_pack,
        (spec((N_LEAVES, 8), jnp.uint32, one_chip),
         spec((N_LEAVES >> 1, 8), jnp.uint32, one_chip)),
        spec((4096,), jnp.int32, one_chip))


@pytest.mark.parametrize("rows,nblocks", [(128, 1), (4096, 4)])
def test_sha3_trie_batch_kernels(one_chip, rows, nblocks):
    """The SHA3 level hash and the fused hash+compare the state engine
    launches (hash-floor bucket of leaves; a wide level of branches)."""
    from plenum_tpu.ops import sha3, trie_jax
    blocks = spec((rows, nblocks, 17, 2), jnp.uint32, one_chip)
    nvalid = spec((rows,), jnp.int32, one_chip)
    compile_for_chip(sha3._sha3_blocks, blocks, nvalid, nblocks=nblocks)
    compile_for_chip(trie_jax._sha3_blocks_eq, blocks, nvalid,
                     spec((rows, 32), jnp.uint8, one_chip), nblocks=nblocks)


def test_bls_g1_aggregate_kernel(one_chip):
    """n=4 signers x 256 jobs (the 4-node committee)."""
    from plenum_tpu.ops import bls381_jax as bj
    compile_for_chip(bj._aggregate_kernel,
                     *bls_aggregate_specs(256, 4, one_chip))


def test_sharded_merkle_build_takes_the_xla_expression(four_chips):
    """A Mosaic kernel cannot be partitioned automatically: the TPU
    compiler refuses the mesh-sharded fused build with the Pallas
    backend, so sharded builds route to the XLA expression
    (DeviceMerkleTree._run_build) — which must partition over four
    chips. Kept small: the refusal is raised at lowering."""
    from plenum_tpu.ops import merkle
    dp, _rep = four_chips
    args = (spec((1 << 14, 1, 16), jnp.uint32, dp),
            spec((1 << 14,), jnp.int32, dp))
    with pytest.raises(NotImplementedError, match="shard_map"):
        merkle._build_levels.lower(*args, nblocks=1, depth=14,
                                   backend="pallas")
    compile_for_chip(merkle._build_levels, *args, nblocks=1, depth=14,
                     backend="plain")


# --------------------------------------------- slow: run before a chip call

@pytest.mark.slow
def test_ed25519_pallas_block(one_chip):
    """One 4,096-signature block — the daemon's bucket. ~3 min, 27 MB
    of code; keyed on n_blocks, so every new bucket is another one."""
    from plenum_tpu.ops import ed25519_pallas as edp
    compile_for_chip(edp._build_verify(1), *ed25519_specs(4096, one_chip),
                     pallas=True)


@pytest.mark.slow
def test_ed25519_xla_sub_block(one_chip):
    from plenum_tpu.ops import ed25519_jax as edj
    compile_for_chip(edj._verify_kernel, *ed25519_specs(1024, one_chip))


@pytest.mark.slow
def test_ed25519_xla_mesh_shape(four_chips):
    """4 x 4,096: the daemon's bucket scaled over the mesh."""
    from plenum_tpu.ops import ed25519_jax as edj
    dp, _rep = four_chips
    compile_for_chip(edj._verify_kernel, *ed25519_specs(16384, dp))


@pytest.mark.slow
@pytest.mark.parametrize("n", [25, 100])
def test_bls_g1_aggregate_committees(one_chip, n):
    from plenum_tpu.ops import bls381_jax as bj
    compile_for_chip(bj._aggregate_kernel,
                     *bls_aggregate_specs(256, n, one_chip))


@pytest.mark.slow
def test_bls_pairing_bucket(one_chip):
    """8 jobs x 2 pairs — the Miller loop + shared final exponentiation."""
    from plenum_tpu.ops import bls381_pairing as bp
    compile_for_chip(bp._pairing_kernel,
                     *specs_like(bp._pack_pair_arrays([], 8, 2), one_chip))


@pytest.mark.slow
def test_mesh_merkle_build_and_gather_2_20(four_chips):
    from plenum_tpu.ops import merkle
    dp, rep = four_chips
    compile_for_chip(merkle._build_levels,
                     spec((N_LEAVES, 1, 16), jnp.uint32, dp),
                     spec((N_LEAVES,), jnp.int32, dp),
                     nblocks=1, depth=20, backend="plain")
    compile_for_chip(
        merkle._gather_pack,
        (spec((N_LEAVES, 8), jnp.uint32, rep),
         spec((N_LEAVES >> 1, 8), jnp.uint32, rep)),
        spec((4096,), jnp.int32, dp))
