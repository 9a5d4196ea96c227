"""``chip_smoke.py``: its CPU rehearsal passes every phase, and off the chip
without ``--rehearse`` (or without the repository's sources) it fails
without printing a result."""
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load(path):
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    """The script as a module, its compile cache in a temporary
    directory, and JAX's cache directory restored afterwards."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    old = jax.config.jax_compilation_cache_dir
    yield _load(ROOT / "chip_smoke.py")
    jax.config.update("jax_compilation_cache_dir", old)


def test_rehearsal_passes_every_phase(smoke, capsys):
    assert smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [ln[:3] for ln in lines[:-1]] == ["[a]", "[b]", "[c]", "[d]",
                                            "[e]"]
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    serve = json.loads(lines[1][4:])
    assert serve["finished"] == serve["requests"] == 8
    cycle = json.loads(lines[2][4:])
    assert cycle["migrated_blocks"] > 0
    assert cycle["migration_kernel"] == "interpret"


def test_no_chip_is_an_error(smoke, capsys):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path, capsys):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    assert _load(tmp_path / "chip_smoke.py").main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
