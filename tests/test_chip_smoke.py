"""``chip_smoke.py`` is the repository's proof that it runs on a TPU: on
any other backend it must fail, and print no result."""
import importlib.util
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def test_chip_smoke_fails_without_a_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
