"""CPU tests of the repairs the first chip runs forced (CHANGES.md
PR 21): one compile-cache home, AOT programs loaded for the devices
they execute on, one chip per spawned replica, Pallas kernels split
over a mesh without ``custom_partitioning``, and the HLO text jax
0.9.0 prints."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ compile cache

# (cache_dir() itself: tests/test_ckpt.py
# test_cache_dir_honors_jax_env_var.)

def test_train_entry_point_enables_the_cache(monkeypatch, tmp_path):
    """``python train.py`` used to run with no persistent cache at
    all: main() must leave jax pointed at ``cache_dir()`` — here the
    directory the environment names, which only main() can have put
    there."""
    import tpunet.main as entry
    from tpunet.utils.cache import (cache_dir,
                                    enable_persistent_compile_cache)

    class StopHere(Exception):
        pass

    def no_trainer(cfg):
        raise StopHere

    monkeypatch.setattr(entry, "Trainer", no_trainer)
    elsewhere = str(tmp_path / "cache")
    assert jax.config.jax_compilation_cache_dir != elsewhere
    with monkeypatch.context() as m:
        m.setenv("JAX_COMPILATION_CACHE_DIR", elsewhere)
        try:
            with pytest.raises(StopHere):
                entry.main(["--dataset", "synthetic", "--checkpoint-dir",
                            str(tmp_path)])
            assert jax.config.jax_compilation_cache_dir == elsewhere
            assert cache_dir() == elsewhere
        finally:
            m.undo()
            enable_persistent_compile_cache()   # back to the suite's


def test_compile_stats_line_counts_compiles():
    from tpunet.utils.cache import (compile_stats_line,
                                    enable_persistent_compile_cache)
    import re
    enable_persistent_compile_cache()

    def programs():
        return int(re.match(r"Compile: (\d+) programs",
                            compile_stats_line()).group(1))
    x = jnp.arange(7.0)
    before = programs()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    assert programs() == before + 1


# ------------------------------------------------------------ AOT store

def test_aot_store_one_device_program_in_multi_device_process(tmp_path):
    """jax 0.9.0 loads a deserialized program for EVERY local device
    unless told otherwise; a one-device program then refuses its first
    call ("expected 8 shards"). The store loads for the devices the
    program executes on — and the loaded program must RUN."""
    from tpunet.utils.cache import AotProgramStore, serializable_compile

    assert jax.local_device_count() > 1
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)

    def fn(a, b):
        return {"sum": a @ b.T, "max": jnp.max(a)}

    with serializable_compile():
        compiled = jax.jit(fn).lower(x, x).compile()
    store = AotProgramStore(str(tmp_path), "digest")
    assert store.devices == jax.local_devices()[:1]
    assert store.save("prog", "s", compiled) is True
    loaded = AotProgramStore(str(tmp_path), "digest").load("prog", "s")
    assert loaded is not None
    got, want = loaded(x, x), fn(x, x)
    np.testing.assert_allclose(np.asarray(got["sum"]),
                               np.asarray(want["sum"]))
    assert float(got["max"]) == float(want["max"])
    # The key follows the execution devices, not the process's count:
    # a two-device store never sees the one-device entry.
    two = AotProgramStore(str(tmp_path), "digest",
                          devices=jax.local_devices()[:2])
    assert two.load("prog", "s") is None


# ----------------------------------------------------------- supervisor

def test_supervisor_gives_each_child_its_own_chip(monkeypatch):
    """N children, N distinct chips, one chip each — read from the
    environments spawn() passes (no chip needed)."""
    from tpunet.router.supervisor import Supervisor
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    sup = Supervisor(["--vit-hidden", "32"])
    envs = [sup.child_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for env in envs:
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # Co-hosted children never share a runtime port.
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    # An operator's own assignment wins.
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2")
    env = sup.child_env(0)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert "TPU_PROCESS_BOUNDS" not in env


def test_supervisor_spawn_passes_the_pinned_environment(monkeypatch,
                                                        tmp_path):
    import subprocess

    from tpunet.router import supervisor as sv
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    seen = []

    class FakeProc:
        pid = 1

        def poll(self):
            return 0

    def fake_popen(argv, env=None, **kw):
        seen.append((argv, env))
        return FakeProc()

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    sup = sv.Supervisor([], directory=str(tmp_path))
    for i in range(3):
        sup.spawn(i)
    assert [env["TPU_VISIBLE_CHIPS"] for _, env in seen] == ["0", "1",
                                                             "2"]
    assert all("tpunet.serve" in argv for argv, _ in seen)


def test_router_parent_initialises_no_backend():
    """The router parent holds no chip: importing its entry point (and
    the supervisor) must not initialise a jax backend."""
    import subprocess
    import sys
    code = ("import tpunet.router.__main__, tpunet.router.supervisor\n"
            "import sys\n"
            "xb = sys.modules.get('jax._src.xla_bridge')\n"
            "print('BACKENDS', 0 if xb is None else len(xb._backends))")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BACKENDS 0" in out.stdout


# ----------------------------------------- kernels split over the mesh

def _mesh(n):
    from tpunet.config import MeshConfig
    from tpunet.parallel import make_mesh
    return make_mesh(MeshConfig(data=n), devices=jax.devices()[:n])


def test_sharded_kernel_is_plain_without_a_mesh_or_a_fit():
    """No kernel mesh, a one-device mesh, or a batch the data axis does
    not divide: the kernel is called as it is."""
    from tpunet.ops.partition import kernel_mesh, sharded
    calls = []

    def kernel(x, flag):
        calls.append((x.shape, flag))
        return x + 1

    fn = sharded(kernel, (P("data", None),), P("data", None))
    x = jnp.zeros((8, 3))
    fn(x, "static")
    with kernel_mesh(_mesh(1)):
        fn(x, "static")
    with kernel_mesh(_mesh(4)):
        fn(jnp.zeros((6, 3)), "static")        # 6 % 4 != 0
        assert calls == [((8, 3), "static")] * 2 + [((6, 3), "static")]
        out = jax.jit(fn, static_argnums=1)(x, "static")
    assert calls[-1] == ((2, 3), "static")       # per-shard rows
    assert out.shape == (8, 3)


# ------------------------------------------------------------ HLO bytes

def test_hlo_bytes_counts_untyped_operands():
    """jax 0.9.0 prints ``dot(%x.1, %w.1)`` — operands by name, no
    types. Their bytes come from the instructions that defined them;
    the older typed form still sums as printed."""
    from tpunet.obs import hlo_bytes
    untyped = """HloModule m

ENTRY %main.1 (x.1: f32[256,128], w.1: f32[128,64]) -> f32[256,64] {
  %x.1 = f32[256,128]{1,0} parameter(0)
  %w.1 = f32[128,64]{1,0} parameter(1)
  ROOT %dot.1 = f32[256,64]{1,0} dot(%x.1, %w.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    typed = untyped.replace(
        "dot(%x.1, %w.1)",
        "dot(f32[256,128]{1,0} %x.1, f32[128,64]{1,0} %w.1)")
    want = 4 * (256 * 128 + 128 * 64 + 256 * 64)
    assert hlo_bytes.breakdown(untyped)["total"] == want
    assert hlo_bytes.breakdown(typed)["total"] == want


# --------------------------------------------- no CPU run of a TPU bench

def test_measurement_scripts_refuse_the_cpu(monkeypatch, capsys):
    """A measurement path that finds no chip fails: bench.py (without
    --smoke) and the scripts' shared gate exit 2 on the CPU backend,
    and say what they found."""
    import sys
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    sys.path.insert(0, REPO)
    import bench
    from _chip import require_tpu

    with pytest.raises(SystemExit) as ei:
        require_tpu()
    assert ei.value.code == 2
    assert "'platform': 'cpu'" in capsys.readouterr().err

    monkeypatch.setattr(sys, "argv", ["bench.py", "--peak-only"])
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 2
    assert "measures the TPU" in capsys.readouterr().err


def test_chip_smoke_refuses_off_the_chip(tmp_path):
    """chip_smoke.py never passes off the chip: with the kernel escape
    hatch in the environment it refuses to start, and with no TPU its
    first child fails — both print '"ok": false' last and exit
    non-zero, and no phase ran on the CPU."""
    import json
    import subprocess
    import sys
    smoke = os.path.join(REPO, "chip_smoke.py")
    env = dict(os.environ, TPUNET_FLASH_INTERPRET="1")
    out = subprocess.run([sys.executable, smoke, "--out", str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert json.loads(out.stdout.splitlines()[-1])["ok"] is False
    assert "refusing to start" in out.stdout

    env = {k: v for k, v in os.environ.items()
           if k != "TPUNET_FLASH_INTERPRET"}
    out = subprocess.run([sys.executable, smoke, "--out", str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    last = json.loads(out.stdout.splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] is None
    assert "PHASE FAILED kernels" in out.stdout
