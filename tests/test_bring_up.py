"""What PR 21 (chip bring-up) left behind that a CPU can check: the chip
smoke refuses to run without a TPU, the compile cache can be placed from
outside, state placed on the mesh compiles the step once, and a native
library is trusted only with a stamp that names its sources.  Since PR 51
also the order in which the suite's files start (``tests/conftest.py::
LONGEST_FIRST``), and since PR 55 the environment its XLA:CPU programs are
compiled in."""

import json
import os
import subprocess
import sys
import tempfile
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    """No TPU, no run: non-zero, the platform named, nothing built and no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
    assert proc.stdout == ""


def test_compile_cache_placement(monkeypatch, tmp_path):
    from horovod_tpu.common import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # Placed from outside: JAX reads the variable itself; the helper
        # must set no other directory.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "out"))
        assert compile_cache.enable_compile_cache() == str(tmp_path / "out")
        assert jax.config.jax_compilation_cache_dir == before

        # Not placed, on the CPU: left off (XLA:CPU executables do not
        # travel between hosts, and the cache sits in the tree).
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

        # Not placed, on an accelerator: <checkout>/.jax_cache, the same
        # from any working directory.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        dirs = []
        for cwd in (tmp_path, REPO):
            monkeypatch.chdir(cwd)
            dirs.append(compile_cache.enable_compile_cache())
            assert jax.config.jax_compilation_cache_dir == dirs[-1]
        assert dirs[0] == dirs[1] == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("placed", ["environment", "checkout", "cpu"])
def test_compile_cache_keeps_small_programs(monkeypatch, tmp_path, placed):
    """Where the cache is on, every program goes into it (JAX's defaults
    leave out what compiles in under a second: a job's small programs
    compile again in every run); where it is left off, nothing is set."""
    from horovod_tpu.common import compile_cache

    options = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in options}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if placed == "environment":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    elif placed == "checkout":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        path = compile_cache.enable_compile_cache()
        if placed == "cpu":
            assert path is None
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 1
            assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
        else:
            assert path == (str(tmp_path) if placed == "environment"
                            else compile_cache.default_cache_dir())
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
            assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    finally:
        for name, value in before.items():
            jax.config.update(name, value)


def test_quick_start_compiles_the_step_once():
    """README quick start on four devices: state replicated on the mesh
    before step 1 leaves ONE compilation (arrays left on device 0 compile
    the step again for its own mesh-replicated outputs)."""
    import horovod_tpu.jax as hvd

    hvd.init()
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((jnp.tanh(x @ params["w"]) @ params["v"] - y) ** 2)

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
              "v": jnp.asarray(rng.standard_normal((16, 1)), jnp.float32)}
    opt = hvd.DistributedOptimizer(optax.adam(1e-2))
    step = hvd.make_train_step(loss_fn, opt, mesh)
    params = hvd.broadcast_parameters(params, root_rank=0)
    params = jax.device_put(params, replicated)
    opt_state = jax.device_put(opt.init(params), replicated)
    losses = []
    for _ in range(3):
        batch = (rng.standard_normal((16, 8)).astype(np.float32),
                 np.ones((16, 1), np.float32))
        params, opt_state, loss = step(
            params, opt_state, jax.device_put(batch, sharded))
        losses.append(float(loss))
    assert step._cache_size() == 1
    assert all(np.isfinite(losses))


def test_native_lib_rebuilt_when_stamp_mismatches(monkeypatch, tmp_path):
    """``*.so`` is not tracked by git: a library on disk is loaded only
    when the digest stamped beside it names the sources beside it."""
    from horovod_tpu.common import native_build

    cpp = tmp_path / "cpp"
    cpp.mkdir()
    (cpp / "Makefile").write_text(
        "libhorovod_core.so: engine.cc\n\tcp engine.cc libhorovod_core.so\n")
    (cpp / "engine.cc").write_text("v1\n")
    monkeypatch.setattr(native_build, "_cpp_dir", lambda: str(cpp))
    monkeypatch.setattr(native_build, "_build_failed", False)
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    lib = cpp / "libhorovod_core.so"

    # A library with no stamp (built by hand, or copied in) is not trusted.
    lib.write_text("stale\n")
    assert native_build.native_lib_path() is None
    assert native_build.ensure_native_lib() == str(lib)
    assert lib.read_text() == "v1\n"
    assert native_build.native_lib_path() == str(lib)

    # Sources change under a library whose mtime says it is newer: the
    # stamp no longer matches, so it is rebuilt from what is on disk.
    (cpp / "engine.cc").write_text("v2\n")
    os.utime(lib, (2**31 - 1, 2**31 - 1))
    assert native_build.native_lib_path() is None
    assert native_build.ensure_native_lib() == str(lib)
    assert lib.read_text() == "v2\n"


# -- the suite's own harness: the order in which its files start (PR 51), and
# -- the environment its programs are compiled in (PR 55) ---------------------

@pytest.fixture
def harness(request):
    """``tests/conftest.py`` as pytest loaded it (``tests/benchmark`` has a
    ``conftest`` of its own, so the module's bare name may be either)."""
    return request.config.pluginmanager.get_plugin(
        os.path.join(REPO, "tests", "conftest.py"))


_SUITE_ENVIRONMENT = ("XLA_FLAGS", "JAX_DISABLE_MOST_OPTIMIZATIONS",
                      "TF_CPP_MIN_LOG_LEVEL", "JAX_COMPILATION_CACHE_DIR",
                      "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                      "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES")


def _environment_after_conftest(cwd, **callers):
    """What a fresh interpreter started in ``cwd`` with the caller's
    variables ``callers``, and none of the suite's, holds once it has
    imported ``tests/conftest.py``."""
    env = {k: v for k, v in os.environ.items() if k not in _SUITE_ENVIRONMENT}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import json, os, sys\n"
         f"sys.path.insert(0, {os.path.join(REPO, 'tests')!r})\n"
         "import conftest\n"
         f"print(json.dumps({{k: os.environ.get(k) "
         f"for k in {_SUITE_ENVIRONMENT!r}}}))"],
        env=dict(env, JAX_PLATFORMS="cpu", **callers), cwd=cwd,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("callers", ["nothing", "level", "directory",
                                     "a_timed_window"])
def test_the_suites_environment(harness, tmp_path, callers):
    """The suite compiles its XLA:CPU programs at backend level 0 (JAX's
    ``jax_disable_most_optimizations``) and keeps them in ONE directory
    outside the checkout that does not move: the same string from any working
    directory, in any process, at any time.  A caller's own level and
    directory win, and so does a test that times a window of steps
    (``benchmark/run.py::run``): its programs are optimised."""
    if callers == "a_timed_window":
        from benchmark import run

        def level_is_cheap():
            return jax.config.read("jax_disable_most_optimizations")

        assert level_is_cheap() == (
            os.environ.get("JAX_DISABLE_MOST_OPTIMIZATIONS") == "1")
        with harness.optimised():
            assert level_is_cheap() is False
        assert level_is_cheap() == (
            os.environ.get("JAX_DISABLE_MOST_OPTIMIZATIONS") == "1")
        # The harness's entry is wrapped in it for the session, and in
        # nothing else.
        assert run.run.__wrapped__.__code__.co_filename == os.path.join(
            REPO, "benchmark", "run.py")
        return
    level, devices = ("--xla_backend_optimization_level=",
                      "--xla_force_host_platform_device_count=8")
    if callers == "nothing":
        one, other = (_environment_after_conftest(cwd)
                      for cwd in (tmp_path, REPO))
        assert one == other                 # two pids, two times, two places
        cache = one["JAX_COMPILATION_CACHE_DIR"]
        assert cache == harness.suite_cache_dir()
        assert os.path.dirname(cache) == tempfile.gettempdir()
        assert not os.path.realpath(cache).startswith(
            os.path.realpath(REPO) + os.sep)
        assert one["XLA_FLAGS"] == devices
        assert one["JAX_DISABLE_MOST_OPTIMIZATIONS"] == "1"    # level 0
        # Every program goes in, however small (JAX's defaults keep out
        # what compiles in under a second: most of the suite's).
        assert one["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
        assert one["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "-1"
        # And a hit's two log lines fill no worker's unread pipe.
        assert one["TF_CPP_MIN_LOG_LEVEL"] == "3"
    elif callers == "level":
        got = _environment_after_conftest(tmp_path, XLA_FLAGS=level + "2")
        assert got["XLA_FLAGS"].split() == [level + "2", devices]
        assert got["JAX_DISABLE_MOST_OPTIMIZATIONS"] is None
        assert got["JAX_COMPILATION_CACHE_DIR"] == harness.suite_cache_dir()
    else:
        mine = str(tmp_path / "mine")
        got = _environment_after_conftest(
            tmp_path, JAX_COMPILATION_CACHE_DIR=mine)
        assert got["JAX_COMPILATION_CACHE_DIR"] == mine
        assert got["JAX_DISABLE_MOST_OPTIMIZATIONS"] == "1"


def test_every_row_of_longest_first_is_a_file_that_collects(harness):
    """A file that was renamed or split leaves no dead row: each path of the
    table is listed once and holds at least one test."""
    table = harness.LONGEST_FIRST
    assert table and len(set(table)) == len(table)
    assert not [path for path in table
                if not os.path.isfile(os.path.join(REPO, path))]
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", *table],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    collected = [line.split("::")[0] for line in proc.stdout.splitlines()
                 if "::" in line]
    assert set(collected) == set(table)
    # The table's order is the order they are queued in.
    assert list(dict.fromkeys(collected)) == list(table)


def test_longest_first_moves_whole_files_and_keeps_their_own_order(harness):
    first, second = harness.LONGEST_FIRST[0], harness.LONGEST_FIRST[-1]

    def items(path, *names):
        return [types.SimpleNamespace(path=os.path.join(REPO, path), name=n)
                for n in names]

    collected = (items("tests/test_zzz.py", "b", "a")
                 + items(second, "y", "x", "z")
                 + items("tests/test_aaa.py", "d", "c")
                 + items(first, "q", "p"))
    ordered = harness.longest_first(collected)
    assert [(os.path.relpath(item.path, REPO), item.name)
            for item in ordered] == (
        [(first, "q"), (first, "p")] + [(second, n) for n in "yxz"]
        + [("tests/test_zzz.py", "b"), ("tests/test_zzz.py", "a"),
           ("tests/test_aaa.py", "d"), ("tests/test_aaa.py", "c")])
    assert collected[0].name == "b"        # a new list; the old one is as it was
