"""The built-kernel store (ops/kernel_store.py) on the CPU backend, with
a small jitted function in the kernel's place: the round trip between
processes, the key, every way a stored file can be unusable, the
known-answer launch, and that ``ed25519_pallas._build_verify`` reaches
the store only where it observes a TPU.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import plenum_tpu.ops.ed25519_jax as edj
import plenum_tpu.ops.ed25519_pallas as edp
from plenum_tpu.ops import kernel_store as ks
from plenum_tpu.ops import mesh as mesh_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VERSIONS = {"jax": "0.9.0", "jaxlib": "0.9.0", "libtpu": "0.0.34",
            "runtime": "built on a monday"}
X = np.arange(8, dtype=np.int32)


class Kernel:
    """x * 2 + y under jax.jit, counting how often its Python runs."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self.traced = 0

        def body(x, y):
            self.traced += 1
            return x * 2 + y

        self._jitted = jax.jit(body)
        self._specs = [jax.ShapeDtypeStruct((8,), jnp.int32)] * 2

    def build(self):
        return self._jitted.lower(*self._specs).compile()


def right(fn):
    return np.asarray(fn(X, X)).tolist() == (3 * X).tolist()


def key_of(tmp_path, versions=None, n=8):
    src = tmp_path / "kernel_src.py"
    if not src.exists():
        src.write_text("BODY = 1\n")
    return ks.kernel_key([str(src)], {"n": n}, versions or VERSIONS, "cpu")


def stored(tmp_path):
    """A store holding one kernel built for key_of(tmp_path)."""
    store = ks.KernelStore(str(tmp_path / "kernels"))
    store.load_or_build("demo-8", key_of(tmp_path), Kernel().build, right)
    assert store.counts()["built"] == 1
    return ks.KernelStore(store.directory)


# ------------------------------------------------------- between processes

CHILD = '''
import json, sys
sys.path.insert(0, %(root)r)
import numpy as np, jax, jax.numpy as jnp
from plenum_tpu.ops import kernel_store as ks
traced = []
def body(x, y):
    traced.append(1)
    return x * 2 + y
jitted = jax.jit(body)
specs = [jax.ShapeDtypeStruct((8,), jnp.int32)] * 2
x = np.arange(8, dtype=np.int32)
store = ks.KernelStore(sys.argv[1])
key = ks.kernel_key([__file__], {"n": 8}, ks.runtime_versions(), "cpu")
fn = store.load_or_build(
    "demo-8", key, lambda: jitted.lower(*specs).compile(),
    lambda f: np.asarray(f(x, x)).tolist() == (3 * x).tolist())
print(json.dumps({"out": np.asarray(fn(x, x + 1)).tolist(),
                  "traced": len(traced), "counts": store.counts()}))
'''


def test_second_process_loads_and_never_runs_the_traced_body(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(CHILD % {"root": ROOT})
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def child():
        p = subprocess.run(
            [sys.executable, str(script), str(tmp_path / "kernels")],
            capture_output=True, text=True, timeout=300, env=env)
        assert p.returncode == 0, p.stderr[-3000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    first, second = child(), child()
    assert first["traced"] == 1 and second["traced"] == 0
    assert first["out"] == second["out"] == (3 * X + 1).tolist()
    assert (first["counts"]["built"], first["counts"]["loaded"]) == (1, 0)
    assert (second["counts"]["built"], second["counts"]["loaded"]) == (0, 1)
    assert first["counts"]["rebuilt"] == second["counts"]["rebuilt"] == {}
    assert first["counts"]["build_s"] > 0 and second["counts"]["load_s"] > 0
    assert os.listdir(tmp_path / "kernels") == ["demo-8.bin"]


def test_loaded_in_the_same_process_too(tmp_path):
    store = stored(tmp_path)
    kernel = Kernel()
    fn = store.load_or_build("demo-8", key_of(tmp_path), kernel.build, right)
    assert right(fn) and kernel.traced == 0
    assert store.counts()["loaded"] == 1 and store.counts()["built"] == 0


# ----------------------------------------------------------------- the key

def real_key(tmp_path, **changes):
    """The ed25519 kernel's key as _stored_verify composes it, on
    copies of its two source files with `changes` applied."""
    args = dict(pallas=b"", jax=b"", n_blocks=1, versions=VERSIONS,
                device_kind="TPU v5 lite", where="a")
    args.update(changes)
    folder = tmp_path / args["where"]
    folder.mkdir(exist_ok=True)
    paths = []
    for name, extra in (("ed25519_pallas.py", args["pallas"]),
                        ("ed25519_jax.py", args["jax"])):
        with open(os.path.join(os.path.dirname(edp.__file__), name),
                  "rb") as f:
            body = f.read()
        (folder / name).write_bytes(body + extra)
        paths.append(str(folder / name))
    return ks.kernel_key(
        paths, {"n_blocks": args["n_blocks"],
                "vmem_limit_bytes": edp.VMEM_LIMIT_BYTES},
        args["versions"], args["device_kind"])["digest"]


@pytest.mark.parametrize("changes", [
    {"pallas": b"#"}, {"jax": b"\n"}, {"n_blocks": 2},
    {"versions": dict(VERSIONS, jax="0.9.1")},
    {"versions": dict(VERSIONS, jaxlib="0.9.1")},
    {"versions": dict(VERSIONS, libtpu="0.0.35")},
    {"versions": dict(VERSIONS, runtime="built on a tuesday")},
    {"device_kind": "TPU v6 lite"},
], ids=["pallas_source", "jax_source", "n_blocks", "jax", "jaxlib",
        "libtpu", "runtime", "device_kind"])
def test_key_changes_with_each_of_its_parts(tmp_path, changes):
    assert real_key(tmp_path, **changes) != real_key(tmp_path)


def test_key_is_content_not_checkout_path(tmp_path):
    assert real_key(tmp_path, where="checkout_one") \
        == real_key(tmp_path, where="checkout_two")


def test_vmem_limit_is_in_the_key(tmp_path, monkeypatch):
    before = real_key(tmp_path)
    monkeypatch.setattr(edp, "VMEM_LIMIT_BYTES", 64 * 1024 * 1024)
    assert real_key(tmp_path) != before


# ------------------------------------------------- files that cannot serve

def damage_truncated(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 100)


def damage_foreign(path):
    with open(path, "wb") as f:
        f.write(os.urandom(4096))


def damage_empty(path):
    open(path, "wb").close()


def damage_flipped_payload_byte(path):
    with open(path, "r+b") as f:
        f.seek(-50, os.SEEK_END)
        byte = f.read(1)
        f.seek(-50, os.SEEK_END)
        f.write(bytes([byte[0] ^ 0xFF]))


def damage_payload_is_not_an_executable(path):
    import hashlib
    import pickle
    payload = pickle.dumps((b"not an executable", None, None))
    with open(path, "rb") as f:
        header = json.loads(f.readline())
    header.update(length=len(payload),
                  sha256=hashlib.sha256(payload).hexdigest())
    with open(path, "wb") as f:
        f.write(json.dumps(header).encode() + b"\n" + payload)


@pytest.mark.parametrize("damage, reason", [
    (damage_truncated, ks.REASON_UNREADABLE),
    (damage_foreign, ks.REASON_UNREADABLE),
    (damage_empty, ks.REASON_UNREADABLE),
    (damage_flipped_payload_byte, ks.REASON_UNREADABLE),
    (damage_payload_is_not_an_executable, ks.REASON_LOAD_FAILED),
], ids=lambda v: v.__name__[7:] if callable(v) else v)
def test_unusable_file_is_rebuilt_and_counted_never_served(
        tmp_path, damage, reason):
    store = stored(tmp_path)
    damage(store.path("demo-8"))
    kernel = Kernel()
    fn = store.load_or_build("demo-8", key_of(tmp_path), kernel.build, right)
    assert right(fn) and kernel.traced == 1
    counts = store.counts()
    assert (counts["loaded"], counts["built"]) == (0, 1)
    assert counts["rebuilt"] == {reason: 1}
    # overwritten: the next process loads it
    again = ks.KernelStore(store.directory)
    kernel = Kernel()
    again.load_or_build("demo-8", key_of(tmp_path), kernel.build, right)
    assert kernel.traced == 0 and again.counts()["loaded"] == 1


@pytest.mark.parametrize("other", [
    {"versions": dict(VERSIONS, libtpu="0.0.35")}, {"n": 16}],
    ids=["libtpu", "params"])
def test_file_written_for_another_key_is_never_loaded(tmp_path, other):
    store = stored(tmp_path)
    kernel = Kernel()
    fn = store.load_or_build("demo-8", key_of(tmp_path, **other),
                             kernel.build, right)
    assert right(fn) and kernel.traced == 1
    assert store.counts()["rebuilt"] == {ks.REASON_KEY: 1}
    assert store.counts()["loaded"] == 0
    # the file now belongs to the other key: the first one rebuilds too
    back = ks.KernelStore(store.directory)
    kernel = Kernel()
    back.load_or_build("demo-8", key_of(tmp_path), kernel.build, right)
    assert kernel.traced == 1
    assert back.counts()["rebuilt"] == {ks.REASON_KEY: 1}


def test_one_edited_byte_of_the_source_rebuilds(tmp_path):
    store = stored(tmp_path)
    (tmp_path / "kernel_src.py").write_text("BODY = 2\n")
    kernel = Kernel()
    store.load_or_build("demo-8", key_of(tmp_path), kernel.build, right)
    assert kernel.traced == 1
    assert store.counts()["rebuilt"] == {ks.REASON_KEY: 1}


def test_wrong_known_answer_discards_the_file(tmp_path):
    store = stored(tmp_path)
    asked = []

    def planted(fn):
        asked.append(fn)
        return False

    kernel = Kernel()
    fn = store.load_or_build("demo-8", key_of(tmp_path), kernel.build,
                             planted)
    assert len(asked) == 1          # the loaded kernel, asked once
    assert fn is not asked[0] and kernel.traced == 1 and right(fn)
    assert store.counts()["rebuilt"] == {ks.REASON_WRONG_ANSWER: 1}
    assert store.counts()["loaded"] == 0


def test_a_store_that_cannot_be_written_still_serves(tmp_path):
    blocker = tmp_path / "kernels"
    blocker.write_text("a file where the directory should be")
    store = ks.KernelStore(str(blocker))
    kernel = Kernel()
    fn = store.load_or_build("demo-8", key_of(tmp_path), kernel.build, right)
    assert right(fn) and store.counts()["built"] == 1
    assert store.counts()["rebuilt"] == {ks.REASON_UNREADABLE: 1}


def test_no_temporary_file_is_left(tmp_path):
    store = stored(tmp_path)
    assert os.listdir(store.directory) == ["demo-8.bin"]


# ------------------------------------------------- the ed25519 kernel's use

def test_known_answer_batch_is_valid_then_three_corruptions():
    from plenum_tpu.crypto.batch_verifier import (
        OpenSSLVerifier, ScalarVerifier)
    from plenum_tpu.crypto.fixtures import make_known_answer_batch
    msgs, sigs, vks = make_known_answer_batch(valid=4)
    items = list(zip(msgs, sigs, vks))
    want = [True] * 4 + [False] * 12
    assert OpenSSLVerifier().verify_batch(items) == want
    assert ScalarVerifier().verify_batch(items) == want
    # s + L is refused on the host (not canonical), not by the curve
    _arrays, valid = edj.host_pack(msgs, sigs, vks)
    assert valid.tolist() == [True] * 8 + [False] * 4 + [True] * 4


def host_verdicts(flip=None):
    """A stand-in for the kernel: the curve's own verdicts of the
    known-answer batch (s + L is the same point, so it passes here),
    padded as the launch is, one of them planted wrong."""
    curve = np.array([True] * 16 + [False] * 16 + [True] * 16
                     + [False] * 16)
    if flip is not None:
        curve[flip] = not curve[flip]

    def fn(*arrays):
        assert all(a.shape[0] == 128 for a in arrays)
        return np.concatenate([curve, np.repeat(curve[:1], 64)])
    return fn


@pytest.mark.parametrize("flip, passes", [
    (None, True), (3, False), (17, False), (63, False)])
def test_known_answer_compares_item_for_item(flip, passes):
    assert edp.known_answer(host_verdicts(flip), 128) is passes


@pytest.mark.parametrize("interpret", [True, False])
def test_build_verify_on_cpu_never_touches_the_store(monkeypatch, interpret):
    def refuse(*a, **k):
        raise AssertionError("the store was reached on the CPU backend")

    monkeypatch.setattr(ks, "default_store", refuse)
    monkeypatch.setattr(ks, "runtime_versions", refuse)
    assert mesh_mod.probe_platform() == "cpu"
    before = ks.counts()
    fn = edp._build_verify.__wrapped__(1, interpret)
    assert hasattr(fn, "lower") and not hasattr(fn, "runtime_executable")
    assert ks.counts() == before


def test_build_verify_on_a_tpu_asks_the_store(monkeypatch, tmp_path):
    """What _build_verify hands the store where it observes a TPU (the
    probe is planted; nothing is traced): the kernel and shape in the
    name, both source files, n_blocks and the VMEM limit in the key."""
    asked = {}

    class Store:
        def load_or_build(self, name, key, build, known_answer):
            asked.update(name=name, key=key)
            return "the stored kernel"

    monkeypatch.setattr(mesh_mod, "probe_platform", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "device_facts",
                        lambda: {"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1})
    monkeypatch.setattr(ks, "runtime_versions", lambda: VERSIONS)
    monkeypatch.setattr(ks, "default_store", Store)
    assert edp._build_verify.__wrapped__(2, False) == "the stored kernel"
    assert asked["name"] == "ed25519_verify-2"
    parts = asked["key"]["parts"]
    assert sorted(parts["sources"]) == ["ed25519_jax.py", "ed25519_pallas.py"]
    assert parts["params"] == {"n_blocks": 2,
                               "vmem_limit_bytes": edp.VMEM_LIMIT_BYTES}
    assert parts["versions"] == VERSIONS
    assert parts["device_kind"] == "TPU v5 lite"
    # interpret mode stays off the store even there
    monkeypatch.setattr(ks, "default_store", None)
    assert hasattr(edp._build_verify.__wrapped__(2, True), "lower")


def test_default_store_lies_beside_the_compile_cache():
    import jax
    store = ks.default_store()
    assert store.directory == os.path.join(
        jax.config.jax_compilation_cache_dir, "kernels")
    assert ks.default_store() is store
    assert set(ks.counts()) == {"loaded", "built", "rebuilt", "load_s",
                                "build_s"}
