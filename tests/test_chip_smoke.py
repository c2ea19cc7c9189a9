"""The bring-up contracts, cheap on the CPU backend:

- ``chip_smoke.py``'s stage functions pass when called small, and its
  ``main`` refuses to run without a TPU;
- the compile-cache placement rule (``runtime/compile_cache.py``);
- ``bench.py`` fails loudly: no TPU and no explicit ``JAX_PLATFORMS=cpu``
  exits non-zero, a measurement that raises ends the run with no JSON;
- ``make_key_mesh`` refuses to shrink a mesh silently.
"""

import os
import subprocess
import sys
import tempfile

import pytest

import chip_smoke  # the conftest puts the checkout on sys.path

REPO = os.path.dirname(os.path.abspath(chip_smoke.__file__))

KEYS, BATCH = 256, 4096


def test_stage_a_small():
    # 40 batches = 128 ms of event time: seven sliding windows per key
    out = chip_smoke.stage_a(0, KEYS, BATCH, 40, sample_keys=16)
    assert out["events"] == 40 * BATCH
    assert out["windows"] > 5 * KEYS
    assert out["Compile_count"] > 0 and out["Device_programs_run"] >= 40


def test_stage_b_small():
    out = chip_smoke.stage_b(0, KEYS, BATCH, 4)
    assert set(out) == {"fused_chain", "stateful_map", "keyed_reduce",
                        "split_reshard", "ysb_chain"}
    assert all(v["rows_equal"] > 0 and v["Device_programs_run"] > 0
               for v in out.values())
    assert out["stateful_map"]["rows_equal"] == 4 * BATCH


@pytest.mark.mesh
def test_stage_d_small():
    out = chip_smoke.stage_d(0, KEYS, BATCH, 12, KEYS, BATCH, 4,
                             sample_keys=16)
    assert out["ffat"]["Mesh_devices"] == 4
    assert out["stateful_map"]["state_devices"] == 4


def test_a_comparison_that_differs_raises():
    import numpy as np
    a = [np.array([1, 2]), np.array([5, 6])]
    b = [np.array([1, 2]), np.array([5, 7])]
    assert chip_smoke.same_rows("same", a, [c[::-1] for c in a]) == 2
    with pytest.raises(chip_smoke.SmokeError, match="1 of 2 rows differ"):
        chip_smoke.same_rows("diff", a, b)
    with pytest.raises(chip_smoke.SmokeError, match="1 rows, reference 2"):
        chip_smoke.same_rows("short", [c[:1] for c in a], a)


def test_numpy_fold_numbers_windows_from_time_zero():
    """The smoke's oracle against a brute-force fold: window ``w`` of
    every key is [w*slide, w*slide+win) from absolute time 0, also for a
    key whose first tuple arrives late in the stream."""
    import numpy as np
    win, slide = 100, 25
    keys = np.array([0, 0, 1, 0, 1], np.int32)
    vals = np.array([1, 2, 4, 8, 16], np.int32)
    ts = np.array([3, 30, 260, 110, 299], np.int64)
    want = {}
    for k, v, t in zip(keys, vals, ts):
        for w in range(0, 20):
            if w * slide <= t < w * slide + win:
                want[(int(k), w)] = want.get((int(k), w), 0) + int(v)
    k, w, v = chip_smoke.numpy_window_fold(
        [({"key": keys[:3], "value": vals[:3]}, ts[:3]),
         ({"key": keys[3:], "value": vals[3:]}, ts[3:])], win, slide)
    assert dict(zip(zip(k.tolist(), w.tolist()), v.tolist())) == want
    assert (1, 7) in want and (1, 0) not in want  # key 1 starts at wid 7


def _run(args):
    return subprocess.run([sys.executable] + args, cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_main_needs_a_tpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert '"ok"' not in r.stdout and "no TPU" in r.stderr


def test_bench_measurement_error_propagates():
    r = _run(["-c",
              "import bench\n"
              "def boom(*a, **k): raise RuntimeError('measurement died')\n"
              "bench._run_config = boom\n"
              "bench.main()\n"])
    assert r.returncode != 0
    assert "measurement died" in r.stderr
    assert "{" not in r.stdout  # no result line


def test_bench_refuses_a_platform_nobody_asked_for(monkeypatch, capsys):
    import bench
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        bench, "_measure_and_report",
        lambda *_: pytest.fail("measured on a platform nobody asked for"))
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_bench_result_names_its_device(monkeypatch, capsys):
    """Under an explicit JAX_PLATFORMS=cpu the metric name says so, and
    the result carries platform / device_kind / device count — and no
    comparison with an assumed baseline."""
    import json

    import jax

    import bench
    monkeypatch.setattr(bench, "_run_config",
                        lambda *a, **k: ([(1e6, 1e3)], 10.0, 20.0, 7))
    monkeypatch.setattr(bench, "_run_op_config", lambda *a, **k: 5e5)
    bench._measure_and_report(jax.devices())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["metric"].endswith("_per_chip (cpu)")
    assert (res["platform"], res["n_devices"]) == ("cpu", len(jax.devices()))
    assert res["device_kind"] == jax.devices()[0].device_kind
    assert not [k for k in res if "baseline" in k or "contended" in k]


def test_no_private_jax_and_no_cpu_rerun_in_the_program():
    """The program imports no private jax module and bench.py starts no
    child (a parent that has touched JAX holds the chip)."""
    import re
    bad = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "__pycache__"]
        for f in files:
            if f.endswith(".py") and f != os.path.basename(__file__):
                with open(os.path.join(root, f)) as fh:
                    if re.search(r"jax\._src|experimental\.shard_map",
                                 fh.read()):
                        bad.append(os.path.join(root, f))
    assert not bad, bad
    with open(os.path.join(REPO, "bench.py")) as fh:
        src = fh.read()
    assert "subprocess" not in src and "os.exec" not in src


def _start_tiny_device_graph(cache_dir=None):
    from windflow_tpu import PipeGraph, Sink_Builder, Source_Builder
    from windflow_tpu.tpu import Map_TPU_Builder

    g = PipeGraph("cc_rule")
    if cache_dir:
        g.with_compile_cache(cache_dir)
    g.add_source(Source_Builder(lambda sh: sh.push({"v": 1}))
                 .with_output_batch_size(8).build()) \
     .add(Map_TPU_Builder(lambda f: {"v": f["v"] + 1}).build()) \
     .add_sink(Sink_Builder(lambda t: None).build())
    g.run()


@pytest.fixture
def restore_cache_dir():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()  # re-open at the restored path


def test_compile_cache_env_places_it(monkeypatch, capsys, restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set: PipeGraph.start sets no directory
    in code, and with_compile_cache is ignored with one log line."""
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    _start_tiny_device_graph(cache_dir="/asked/in/code")
    assert jax.config.jax_compilation_cache_dir is None
    err = capsys.readouterr().err
    assert err.count("ignored") == 1 and "/placed/from/outside" in err


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    import jax
    from windflow_tpu.runtime.compile_cache import DEFAULT_CACHE_DIR
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    _start_tiny_device_graph()
    got = jax.config.jax_compilation_cache_dir
    assert got == DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert not got.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in got
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_with_compile_cache_is_used_when_env_unset(monkeypatch, tmp_path,
                                                   restore_cache_dir):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    _start_tiny_device_graph(cache_dir=str(tmp_path / "cc"))
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cc")


@pytest.mark.mesh
def test_make_key_mesh_refuses_missing_devices():
    import jax
    from windflow_tpu.mesh import make_key_mesh
    from windflow_tpu.mesh.core import set_excluded_devices

    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"needs {2 * n} devices"):
        make_key_mesh(2 * n)
    with pytest.raises(ValueError, match="needs"):
        make_key_mesh(2 * n, shape=(2 * n, 1))
    assert make_key_mesh(n).devices.size == n
    set_excluded_devices([jax.devices()[-1].id])
    try:  # degraded recovery still lands on the survivors
        assert make_key_mesh(n).devices.size == n - 1
        assert make_key_mesh(2 * n).devices.size == n - 1
    finally:
        set_excluded_devices([])
