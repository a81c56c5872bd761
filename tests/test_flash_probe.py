"""`tools/flash_probe.py`'s cases, read on the CPU (the tool times nothing
off the TPU): heads, length, width, then words in any order."""
import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def probe():
    spec = importlib.util.spec_from_file_location(
        "flash_probe", os.path.join(HERE, "tools", "flash_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("text,want", [
    ("72,8192,128,causal,512", dict(heads=72, T=8192, d=128, causal=True,
                                    window=512, block=512, dv=128)),
    ("48,8192,128,causal", dict(heads=48, T=8192, d=128, causal=True,
                                window=None, block=512, dv=128)),
    ("192,1024,64", dict(heads=192, T=1024, d=64, causal=False, window=None,
                         block=512, dv=64)),
    ("32,8192,192,causal,v128", dict(heads=32, T=8192, d=192, causal=True,
                                     window=None, block=512, dv=128)),
    ("32,8192,256,v128,causal,b256", dict(heads=32, T=8192, d=256,
                                          causal=True, window=None,
                                          block=256, dv=128)),
])
def test_cases_read_as_written(probe, text, want):
    assert probe.parse_case(text) == want


@pytest.mark.parametrize("text", ["32,8192", "32,8192,192,vwide",
                                  "32,8192,192,bsmall"])
def test_malformed_cases_are_refused(probe, text):
    with pytest.raises(ValueError):
        probe.parse_case(text)
