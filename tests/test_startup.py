"""Start-up rules (each in a fresh interpreter): importing the package
holds no device, the compile cache stays where it was put, the chip
smoke refuses a machine without a TPU, and a compile-time
RESOURCE_EXHAUSTED is not something to retry."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code_or_args, env_changes, cwd=REPO):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    args = code_or_args if isinstance(code_or_args, list) \
        else ["-c", code_or_args]
    return subprocess.run([sys.executable] + args, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=240)


def test_importing_every_module_initialises_no_backend():
    r = _python(
        "import importlib, pkgutil, spark_rapids_tpu\n"
        "for m in pkgutil.walk_packages(spark_rapids_tpu.__path__,\n"
        "                               'spark_rapids_tpu.'):\n"
        "    importlib.import_module(m.name)\n"
        "import spark_rapids_tpu.session, spark_rapids_tpu.cpu\n"
        "from jax._src import xla_bridge\n"
        "print('BACKENDS', sorted(xla_bridge._backends))\n", {})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BACKENDS []" in r.stdout, r.stdout[-500:]


_PRINT_CACHE_DIR = (
    "import jax, spark_rapids_tpu\n"
    "print('DIR', jax.config.jax_compilation_cache_dir)\n"
    "print('FN', spark_rapids_tpu.compile_cache_dir())\n")


def test_compile_cache_placed_from_outside_stays_put(tmp_path):
    placed = str(tmp_path / "placed")
    r = _python(_PRINT_CACHE_DIR, {"JAX_COMPILATION_CACHE_DIR": placed})
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR {placed}\n" in r.stdout, r.stdout
    assert f"FN {placed}\n" in r.stdout, r.stdout
    # persistence on: the AOT tier must not repoint JAX's cache either
    r = _python(
        "import jax, spark_rapids_tpu\n"
        "from spark_rapids_tpu import persist\n"
        "from spark_rapids_tpu.config import get_conf\n"
        "get_conf().set('spark.rapids.tpu.persist.enabled', True)\n"
        f"get_conf().set('spark.rapids.tpu.persist.dir', {placed!r})\n"
        "assert persist.active() is not None\n"
        "print('DIR', jax.config.jax_compilation_cache_dir)\n",
        {"JAX_COMPILATION_CACHE_DIR": placed})
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR {placed}\n" in r.stdout, r.stdout


def test_compile_cache_defaults_to_the_checkout():
    r = _python(_PRINT_CACHE_DIR, {})
    assert r.returncode == 0, r.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert f"DIR {want}\n" in r.stdout, r.stdout
    assert f"FN {want}\n" in r.stdout, r.stdout


def test_chip_smoke_refuses_a_machine_without_a_tpu(tmp_path):
    r = _python([os.path.join(REPO, "chip_smoke.py")],
                {"TMPDIR": str(tmp_path)})
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr, r.stderr[-2000:]
    assert r.stdout.strip() == "", r.stdout  # no result line
    # it stopped before generating any data
    left = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
            for f in fs]
    assert left == [], left


def test_chip_smoke_ends_with_the_contract_line(monkeypatch, capsys):
    """The parent's last stdout line holds exactly `ok` and `device`
    (`platform`, `kind`, `count`); the long record is the line before."""
    import json

    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def fake_child(which, work):
        return {"device": dict(device), "compile_cache_dir": work,
                "compile": {"persistent_cache_hits": which - 1,
                            "backend_compile_s": 3.0 - which}}

    monkeypatch.setattr(chip_smoke, "_run_child", fake_child)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    record = json.loads(lines[-2])["record"]
    assert record["device"] == device
    assert record["reduced"] == chip_smoke.REDUCED
    assert record["second_process"]["compile"]["persistent_cache_hits"] == 1


def test_compile_time_resource_exhausted_is_fatal():
    from spark_rapids_tpu.execs import retry

    hbm = RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
        "of memory in memory space hbm. Used 64.00G of 15.75G hbm. "
        "Exceeded hbm capacity by 48.25G.")
    vmem = RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem "
        "while allocating on stack for %custom-call")
    for e in (hbm, vmem):
        assert retry.classify(e) == "fatal"
        assert not retry.should_cpu_fallback(e)
    # an allocation that fails at run time is still worth a retry
    run_time = RuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: "
        "Attempting to allocate 8.00G. That was not possible. There "
        "are 5.2G free.")
    assert retry.classify(run_time) == "retryable"
