"""The fused Pallas SHA-256 kernel (ops/sha256_pallas.py) and the
backend routing seam (ops/sha256.select_backend / compress_blocks).

The suite pins JAX_PLATFORMS=cpu, so the KERNEL BODY runs here applied
to one [BLOCK_R, 128] tile block at a time OUTSIDE pallas_call
(`kernel_eager`): the exact kernel program — same tiles, same unrolled
rounds, same ragged-block masking — executed op by op, so every digest
is byte-for-byte the kernel's arithmetic. (``interpret=True`` compiles
the fully unrolled kernel through XLA's CPU backend as ONE program,
which takes many minutes even at the smallest shape; the one case that
does so is marked slow.) What this cannot see — the BlockSpec plumbing
and the Mosaic lowering — is covered where it can now be had:
tests/test_tpu_compile.py compiles the kernel for a described v5e, and
chip_smoke.py compares the compiled kernel with hashlib on the chip.
"""
import hashlib
import random

import numpy as np
import pytest

import jax.numpy as jnp

from plenum_tpu.ops import scatter_ragged_rows
from plenum_tpu.ops import sha256 as sha_mod
from plenum_tpu.ops import sha256_pallas as sp
from plenum_tpu.ops.sha256 import (
    _sha256_blocks, _sha256_blocks_tiled, pad_messages, sha256_many)

# NIST CAVP / FIPS 180-2 known-answer vectors (SHA256ShortMsg.rsp +
# the FIPS appendix examples) — constants, not recomputed, so a wrong
# kernel AND a wrong reference cannot cancel out.
CAVP = [
    (b"",
     "e3b0c44298fc1c149afbf4c8996fb924"
     "27ae41e4649b934ca495991b7852b855"),
    (bytes.fromhex("d3"),
     "28969cdfa74a12c82f3bad960b0b000a"
     "ca2ac329deea5c2328ebc6f2ba9802c1"),
    (bytes.fromhex("11af"),
     "5ca7133fa735326081558ac312c620ee"
     "ca9970d1e70a4b95533d956f072d1f98"),
    (bytes.fromhex("b4190e"),
     "dff2e73091f6c05e528896c4c831b944"
     "8653dc2ff043528f6769437bc7b975c2"),
    (b"abc",
     "ba7816bf8f01cfea414140de5dae2223"
     "b00361a396177a9cb410ff61f20015ad"),
    (b"abcdbcdecdefdefgefghfghighijhijkijkljklmklmnlmnomnopnopq",
     "248d6a61d20638b8e5c026930c3e6039"
     "a33ce45964ff2167f6ecedd419db06c1"),
    (b"abcdefghbcdefghicdefghijdefghijkefghijklfghijklmghijklmn"
     b"hijklmnoijklmnopjklmnopqklmnopqrlmnopqrsmnopqrstnopqrstu",
     "cf5b16a778af8380036ce59e7b049237"
     "0b249b11e8f07a51afac45037afee9d1"),
]


class _OutRef:
    """Stand-in for the kernel's output ref: out_ref[j] = tile."""

    def __init__(self):
        self.tiles = {}

    def __setitem__(self, j, tile):
        self.tiles[j] = np.asarray(tile)


def _eager_blocks(words, nvalid, nblocks):
    """Drop-in for the jitted pallas wrapper (sp._build_sha256(...)):
    the same word-major relayout, the kernel body on each grid block,
    the same un-layout — no pallas_call, no whole-program compile."""
    words = np.asarray(words)
    nvalid = np.asarray(nvalid, dtype=np.int32)
    b = words.shape[0]
    assert b % sp.BLOCK == 0
    nb8 = b // sp.BLOCK_L
    wb = words.reshape(b, nblocks * 16).T.reshape(
        nblocks * 16, nb8, sp.BLOCK_L)
    nvb = nvalid.reshape(1, nb8, sp.BLOCK_L)
    out = np.zeros((8, nb8, sp.BLOCK_L), dtype=np.uint32)
    kernel = sp._sha256_kernel(nblocks)
    for g in range(b // sp.BLOCK):
        rows = slice(g * sp.BLOCK_R, (g + 1) * sp.BLOCK_R)
        ref = _OutRef()
        kernel(jnp.asarray(wb[:, rows]), jnp.asarray(nvb[:, rows]), ref)
        for j in range(8):
            out[j, rows] = ref.tiles[j]
    return out.reshape(8, b).T


def kernel_eager(msgs):
    """sp.sha256_many_pallas with the kernel body run eagerly."""
    words, nvalid, nblocks = pad_messages(msgs)
    b = len(msgs)
    pad = (-b) % sp.BLOCK
    if pad:
        words = np.pad(words, ((0, pad), (0, 0), (0, 0)))
        nvalid = np.pad(nvalid, (0, pad), constant_values=1)
    return sha_mod.digests_to_bytes(
        _eager_blocks(words, nvalid, nblocks)[:b])


def test_cavp_vectors_kernel_body():
    msgs = [m for m, _ in CAVP]
    assert kernel_eager(msgs) == [bytes.fromhex(d) for _, d in CAVP]


@pytest.mark.slow
def test_cavp_vectors_pallas_interpret():
    """The real pallas_call in interpret mode at the smallest shape
    (one grid block, one message block): XLA's CPU backend compiles the
    unrolled kernel as one program — many minutes, hence slow."""
    msgs = [m for m, _ in CAVP if len(m) <= 55]
    got = sp.sha256_many_pallas(msgs, interpret=True)
    assert got == [hashlib.sha256(m).digest() for m in msgs]


def test_cavp_vectors_xla_reference():
    msgs = [m for m, _ in CAVP]
    assert sha256_many(msgs) == [bytes.fromhex(d) for _, d in CAVP]


def test_randomized_ragged_byte_equality():
    """Pallas-interpret vs XLA vs hashlib across ragged lengths —
    including the block-boundary lengths (55/56/63/64/65) and the
    65-byte RFC 6962 node-hash shape."""
    rng = random.Random(42)
    lengths = [0, 1, 54, 55, 56, 63, 64, 65, 119, 120, 127, 128, 129,
               200, 300]
    msgs = [bytes(rng.randrange(256) for _ in range(rng.choice(lengths)))
            for _ in range(257)]
    msgs += [b"\x01" + bytes(64)]  # the node-hash message: 65 bytes
    want = [hashlib.sha256(m).digest() for m in msgs]
    assert kernel_eager(msgs) == want
    assert sha256_many(msgs) == want


@pytest.mark.parametrize("n", [sp.BLOCK - 1, sp.BLOCK, sp.BLOCK + 1])
def test_block_boundary_batches(n):
    """2^k±1 around the kernel's grid block: the internal pad rows
    must never leak into real digests."""
    msgs = [b"txn-%07d" % i for i in range(n)]
    want = [hashlib.sha256(m).digest() for m in msgs]
    assert kernel_eager(msgs) == want


def test_node_pair_shape_matches_tree_hasher():
    """65-byte H(0x01||l||r) node messages through the kernel equal
    the scalar RFC 6962 node hash."""
    rng = random.Random(7)
    pairs = [(bytes(rng.randrange(256) for _ in range(32)),
              bytes(rng.randrange(256) for _ in range(32)))
             for _ in range(64)]
    msgs = [b"\x01" + l + r for l, r in pairs]
    got = kernel_eager(msgs)
    from plenum_tpu.ledger.tree_hasher import TreeHasher
    th = TreeHasher()
    assert got == [th.hash_children(l, r) for l, r in pairs]


def test_tiled_xla_matches_plain():
    """The CPU cache-tiled lowering is the same math: byte-equal
    states for pow2 and padded batch sizes."""
    from plenum_tpu.common.config import Config
    tile = Config.SHA256_CPU_TILE
    msgs = [b"m%d" % i for i in range(2 * tile)]
    words, nvalid, nb = pad_messages(msgs)
    wj, nvj = jnp.asarray(words), jnp.asarray(nvalid)
    plain = np.asarray(_sha256_blocks(wj, nvj, nb))
    tiled = np.asarray(_sha256_blocks_tiled(wj, nvj, nb, tile))
    assert (plain == tiled).all()


def test_routed_dispatch_pads_non_tile_multiple():
    """sha256_many on a batch that is NOT a tile multiple still routes
    through the tiled path (internal pad rows) and matches hashlib."""
    from plenum_tpu.common.config import Config
    n = 2 * Config.SHA256_CPU_TILE + 321
    msgs = [b"x%06d" % i for i in range(n)]
    assert sha256_many(msgs) == [hashlib.sha256(m).digest()
                                 for m in msgs]


def test_select_backend_cpu_routing():
    from plenum_tpu.common.config import Config
    # the suite runs on the CPU backend: pallas stays off, big batches
    # tile, small batches stay plain
    assert sha_mod.select_backend(2 * Config.SHA256_CPU_TILE) == "tiled"
    assert sha_mod.select_backend(16) == "plain"


def test_select_backend_interp_override(monkeypatch):
    monkeypatch.setenv(sp.PALLAS_ENV, "pallas_interp")
    assert sha_mod.select_backend(sp.BLOCK) == "pallas_interp"
    # below a kernel block the override does not apply
    assert sha_mod.select_backend(sp.BLOCK - 1) != "pallas_interp"


def test_interp_override_end_to_end(monkeypatch):
    """The full sha256_many production path with the kernel forced via
    env — the integration seam a TPU host takes (select_backend →
    compress_blocks → sp.sha256_blocks), byte-for-byte; the jitted
    pallas wrapper is swapped for the eager kernel body."""
    monkeypatch.setenv(sp.PALLAS_ENV, "pallas_interp")
    built = []

    def eager_build(n_grid, nblocks, interpret=False):
        built.append((n_grid, nblocks, interpret))
        return lambda w, nv: jnp.asarray(_eager_blocks(w, nv, nblocks))

    monkeypatch.setattr(sp, "_build_sha256", eager_build)
    msgs = [b"leaf-%05d" % i for i in range(sp.BLOCK)]
    assert sha256_many(msgs) == [hashlib.sha256(m).digest()
                                 for m in msgs]
    assert built == [(1, 1, True)]


def test_pallas_probe_registry_shared_reset():
    """The availability registry (satellite: ONE probe for ed25519 +
    sha256) is cleared together with the platform probe — the
    dryrun_multichip reset contract. A step-down is COUNTED, and the
    count outlives the reset: a run cannot forget that a family left
    its device path."""
    from plenum_tpu.ops import mesh as mesh_mod
    # on this suite's CPU backend the kernel reads unavailable
    assert sp.pallas_available() is False
    before = mesh_mod.step_down_counts().get(sp.PALLAS_ENV, 0)
    mesh_mod.disable_pallas_backend(sp.PALLAS_ENV)
    assert sp.pallas_available() is False
    assert mesh_mod.kernel_backends()[sp.PALLAS_ENV] is False
    assert mesh_mod.step_down_counts()[sp.PALLAS_ENV] == before + 1
    assert mesh_mod.mesh_stats()["step_downs"][sp.PALLAS_ENV] == before + 1
    mesh_mod._reset_probe()
    assert sp.PALLAS_ENV not in mesh_mod.kernel_backends()
    assert mesh_mod.step_down_counts()[sp.PALLAS_ENV] == before + 1
    # re-probe repopulates (and stays off on CPU)
    assert sp.pallas_available() is False


@pytest.mark.parametrize("stage", ["compile", "run"])
def test_routed_dispatch_failure_policy(monkeypatch, stage):
    """A Pallas kernel the compiler refuses is a program bug and
    RAISES; a launched kernel that dies at run time steps the family
    down to the XLA expression, counted, and the batch is still
    served."""
    from plenum_tpu.ops import mesh as mesh_mod
    msgs = [b"m%04d" % i for i in range(sp.BLOCK)]
    words, nvalid, nb = pad_messages(msgs)
    wj, nvj = jnp.asarray(words), jnp.asarray(nvalid)
    monkeypatch.setattr(mesh_mod, "_PROVEN", set())
    with mesh_mod._PROBE_LOCK:
        mesh_mod._PALLAS_BACKENDS[sp.PALLAS_ENV] = True   # "on a TPU"
    before = mesh_mod.step_down_counts().get(sp.PALLAS_ENV, 0)

    class _DiesOnDevice:
        def block_until_ready(self):
            raise RuntimeError("induced run-time failure")

    def refused(*a, **k):
        raise NotImplementedError("induced lowering failure")

    try:
        if stage == "compile":
            monkeypatch.setattr(sp, "sha256_blocks", refused)
            with pytest.raises(NotImplementedError):
                sha_mod.sha256_blocks_routed(wj, nvj, nb)
            assert mesh_mod.step_down_counts().get(
                sp.PALLAS_ENV, 0) == before
        else:
            monkeypatch.setattr(sp, "sha256_blocks",
                                lambda *a, **k: _DiesOnDevice())
            out = sha_mod.sha256_blocks_routed(wj, nvj, nb)
            assert sha_mod.digests_to_bytes(np.asarray(out)) == [
                hashlib.sha256(m).digest() for m in msgs]
            assert mesh_mod.step_down_counts()[sp.PALLAS_ENV] \
                == before + 1
            assert sp.pallas_available() is False
    finally:
        mesh_mod._reset_probe()


def test_ed25519_probe_routes_through_registry():
    from plenum_tpu.ops import ed25519_jax as edj
    from plenum_tpu.ops import mesh as mesh_mod
    assert edj._pallas_available() is False  # CPU suite
    assert edj._ED25519_PALLAS_ENV in mesh_mod.kernel_backends()
    mesh_mod._reset_probe()


def test_scatter_ragged_rows_shared_helper():
    msgs = [b"", b"a", b"bc" * 40, b"d" * 7]
    out, lens = scatter_ragged_rows(msgs, 128)
    assert out.shape == (4, 128)
    assert list(lens) == [0, 1, 80, 7]
    for i, m in enumerate(msgs):
        assert out[i, :len(m)].tobytes() == m
        assert not out[i, len(m):].any()


def test_sha3_and_sha256_mixed_padding_share_scatter():
    """Both pad paths ride scatter_ragged_rows: ragged batches through
    each hash still match hashlib exactly."""
    from plenum_tpu.ops.sha3 import sha3_256_many
    rng = random.Random(9)
    msgs = [bytes(rng.randrange(256) for _ in range(n))
            for n in (0, 1, 63, 64, 65, 135, 136, 137, 272, 273)]
    assert sha256_many(msgs) == [hashlib.sha256(m).digest()
                                 for m in msgs]
    assert sha3_256_many(msgs) == [hashlib.sha3_256(m).digest()
                                   for m in msgs]
