"""utils/backend.py: one way to get a device, one compile-cache decision,
one peaks table — plus the native library's build stamp.

Subprocesses here run with ``JAX_PLATFORMS=cpu`` (or a platform name jax
cannot initialize, to prove an import touches no backend); none loads the
TPU library.
"""
import json
import os
import subprocess
import sys

import pytest

from transmogrifai_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    full.update(PYTHONPATH=REPO, **env)
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=full, cwd=REPO, timeout=300)


def test_import_initializes_no_backend_and_sets_no_config():
    # a platform jax cannot initialize: any backend touch at import raises
    r = _py("import jax, transmogrifai_tpu, transmogrifai_tpu.serve, "
            "transmogrifai_tpu.ops.sweep, transmogrifai_tpu.workflow.stream\n"
            "print('CACHE', jax.config.jax_compilation_cache_dir)",
            JAX_PLATFORMS="no_such_platform")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CACHE None" in r.stdout


def test_require_tpu_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as ei:
        backend.require_tpu("some_harness")
    assert ei.value.code not in (0, None)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["ok"] is False
    assert line["device"]["platform"] == "cpu"
    assert "some_harness" in line["error"]


@pytest.mark.parametrize("script", ["bench.py", "scale10m.py", "scale100m.py"])
def test_harness_exits_nonzero_without_tpu(script):
    r = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       capture_output=True, text=True, cwd=REPO, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "TMOG_SCALE_ROWS": "1000"})
    assert r.returncode != 0
    assert json.loads(r.stdout.strip().splitlines()[-1])["ok"] is False


def test_device_summary_reports_what_jax_selected():
    import jax

    dev = backend.device_summary()
    assert dev == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}


def test_compile_cache_off_on_cpu():
    import jax

    assert backend.compile_cache_dir() is None
    assert backend.compile_cache_dir() is None  # idempotent
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_root_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert backend.cache_root() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert backend.cache_root() == "/some/dir"


def test_env_cache_dir_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in code
    and the cache files land where the variable says."""
    d = tmp_path / "jaxcache"
    r = _py("import jax, jax.numpy as jnp\n"
            "from transmogrifai_tpu.utils import backend\n"
            "seen = []\n"
            "orig = jax.config.update\n"
            "jax.config.update = lambda k, v: (seen.append(k), orig(k, v))\n"
            "print('DIR', backend.compile_cache_dir())\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
            ".block_until_ready()\n"
            "print('SET', sorted(set(seen)))",
            JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(d))
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR {d}" in r.stdout
    assert "jax_compilation_cache_dir" not in r.stdout.split("SET")[1]
    assert any(d.iterdir()), "no cache entry landed in the env directory"


def test_named_scope_is_part_of_the_cache_key(tmp_path):
    """Two programs that differ by a ``jax.named_scope`` alone (metadata: the
    names a profiler trace shows) get an entry each once
    ``compile_cache_dir()`` has run, so a directory filled before the scopes
    existed cannot serve executables without them."""
    d = tmp_path / "jaxcache"
    r = _py("import os, jax, jax.numpy as jnp\n"
            "from transmogrifai_tpu.utils import backend\n"
            "backend.compile_cache_dir()\n"
            "n = lambda: len([f for f in os.listdir(os.environ"
            "['JAX_COMPILATION_CACHE_DIR']) if f.endswith('-cache')])\n"
            "x = jnp.ones((32, 32)).block_until_ready()\n"
            "def plain(a):\n"
            "    return jnp.sin(a) @ a\n"
            "def scoped(a):\n"
            "    with jax.named_scope('metrics.rank'):\n"
            "        return jnp.sin(a) @ a\n"
            "scoped.__name__ = 'plain'\n"
            "n0 = n()\n"
            "jax.jit(plain)(x).block_until_ready()\n"
            "n1 = n()\n"
            "jax.jit(scoped)(x).block_until_ready()\n"
            "print('ADDED', n1 - n0, n() - n1)",
            JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(d))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ADDED 1 1" in r.stdout, r.stdout


def test_rowsharded_compiles_skip_the_persistent_cache(tmp_path):
    """``ops/sweep._without_persistent_cache``: nothing compiled inside the
    block is written to (or read from) jax's persistent cache, nesting
    included; compiles after it are cached again."""
    d = tmp_path / "jaxcache"
    r = _py("import os, jax, jax.numpy as jnp\n"
            "from transmogrifai_tpu.ops import sweep\n"
            "from transmogrifai_tpu.utils import backend\n"
            "backend.compile_cache_dir()\n"
            "n = lambda: len(os.listdir(os.environ"
            "['JAX_COMPILATION_CACHE_DIR'])) if os.path.isdir(os.environ"
            "['JAX_COMPILATION_CACHE_DIR']) else 0\n"
            "x = jnp.ones((32, 32)).block_until_ready()\n"
            "n0 = n()\n"
            "with sweep._without_persistent_cache():\n"
            "    with sweep._without_persistent_cache():\n"
            "        jax.jit(lambda a: jnp.sin(a) @ a)(x).block_until_ready()\n"
            "    jax.jit(lambda a: jnp.cos(a) @ a)(x).block_until_ready()\n"
            "print('INSIDE', n() - n0)\n"
            "jax.jit(lambda a: jnp.tan(a) @ a)(x).block_until_ready()\n"
            "print('AFTER', n() > n0)",
            JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(d))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "INSIDE 0" in r.stdout and "AFTER True" in r.stdout


class TestDevicePeaks:
    def test_v5e_kind_has_cited_peaks(self):
        assert backend.device_peaks("TPU v5 lite", "tpu") == {
            "peak_flops": 197e12, "peak_hbm_gbps": 819.0}

    def test_unknown_tpu_kind_is_an_error(self):
        with pytest.raises(KeyError, match="TPU v99"):
            backend.device_peaks("TPU v99", "tpu")
        with pytest.raises(KeyError):
            backend.device_peaks("TPU v99")  # the kind alone says TPU

    def test_cpu_has_no_roof(self):
        assert backend.device_peaks("cpu", "cpu") == {
            "peak_flops": None, "peak_hbm_gbps": None}
        assert backend.device_peaks(None)["peak_flops"] is None

    def test_env_override_covers_an_unknown_kind(self, monkeypatch):
        monkeypatch.setenv("TMOG_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("TMOG_PEAK_HBM_GBPS", "100")
        assert backend.device_peaks("TPU v99", "tpu") == {
            "peak_flops": 1e12, "peak_hbm_gbps": 100.0}


class TestNativeBuild:
    def test_no_host_specific_flags(self):
        from transmogrifai_tpu.native import build

        assert not any(f.startswith(("-march", "-mtune", "-mcpu"))
                       for f in build._CMD)

    def test_stamp_follows_sources_and_machine(self, tmp_path, monkeypatch):
        from transmogrifai_tpu.native import build

        src = tmp_path / "a.cpp"
        src.write_text("int f() { return 1; }\n")
        s1 = build._stamp([str(src)])
        assert s1 == build._stamp([str(src)])
        src.write_text("int f() { return 2; }\n")
        s2 = build._stamp([str(src)])
        assert s2 != s1
        monkeypatch.setattr(build.platform, "node", lambda: "another-host")
        assert build._stamp([str(src)]) != s2

    def test_rebuilds_when_library_is_absent(self, tmp_path, monkeypatch):
        """A library that this machine did not build from these sources is
        never loaded: the name carries the stamp, so a foreign or stale file
        simply is not the one looked for."""
        import shutil

        from transmogrifai_tpu.native import build

        if shutil.which("g++") is None:
            pytest.skip("no g++ here: the Python fallbacks are in use")
        work = tmp_path / "native"
        shutil.copytree(build._SRC_DIR, work / "src")
        (work / "_libtransmog.so").write_bytes(b"built elsewhere")
        monkeypatch.setattr(build, "_DIR", str(work))
        monkeypatch.setattr(build, "_SRC_DIR", str(work / "src"))
        path = build.build()
        assert path is not None and os.path.exists(path)
        assert os.path.basename(path) != "_libtransmog.so"
        assert build._stamp(build._sources()) in path
        # the foreign library was cleared away, and a second call reuses
        assert sorted(p.name for p in work.glob("_libtransmog*")) == [
            os.path.basename(path)]
        mtime = os.path.getmtime(path)
        assert build.build() == path and os.path.getmtime(path) == mtime
