"""The benchmark's tests run on the CPU, on small copies of its cells:
``bench`` is importable from the checkout's root."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for path in (ROOT / "src", ROOT):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

#: qwen15-4b-serve cut to a size a test run can hold; every width is
#: small here, which only the CPU tests do
TINY_QWEN = {"num_hidden_layers": 2, "hidden_size": 64,
             "intermediate_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 4, "vocab_size": 512}


@pytest.fixture
def tiny_run():
    """``run(cell, seconds, trace=False, **kw)`` -> the parsed result of
    one CPU run of a small copy of ``cell``."""
    import json

    from bench import core, harness

    def run(cell, seconds=1.0, trace=False, seed=2**31 + 7, **kw):
        kw.setdefault("overrides", TINY_QWEN if "qwen" in cell else None)
        line = harness.run_cell(cell, seed, seconds, trace,
                                require_tpu=False, compile_cache=False,
                                peak=core.peaks("TPU v5 lite"), **kw)
        return line, json.loads(line)

    return run
