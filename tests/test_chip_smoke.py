"""chip_smoke.py's phases on the CPU: the reduced llama3.2-1b config and
n=64 applications, with the Pallas kernels in interpret mode.  Its main()
refuses any device that is not a TPU."""

import functools
import importlib.util
import pathlib

import pytest

from repro.configs import get_config
from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_reduced(smoke):
    # decode_impl="pallas" interprets the fused kernel off-TPU
    out = smoke.serve_phase(
        get_config("llama3.2-1b").reduced(), prompt_lens=(5, 9, 12, 16),
        gen=6, n_slots=4, max_len=64, page_size=4, bucket=8,
        require_kernel=False,
    )
    for impl in ("xla", "pallas"):
        assert out[impl]["completed"] == 4
        assert out[impl]["prefill_programs"] == 2  # buckets of 8 and 16
        assert out[impl]["decided"] > 0
        assert out[impl]["mismatched"] == 0
    assert out["xla_pallas_agreement"] == 1.0


def test_offload_phase_interpreted(smoke, monkeypatch):
    # the DB resolves its replacements by import path at discover time, so
    # the sessions bind these interpret-mode Pallas targets
    called = []

    def interpreted(name):
        fn = functools.partial(
            getattr(ops, name), backend="pallas", interpret=True
        )

        def run(*args):
            called.append(name)
            return fn(*args)

        monkeypatch.setattr(ops, name, run)

    interpreted("fft2d")
    interpreted("lu_nr_compat")
    rows = smoke.offload_phase((64,), require_kernel=False)
    assert [(r["block"], r["numerics_ok"]) for r in rows] == [
        ("fft2d", True), ("lu", True)
    ]
    assert set(called) == {"fft2d", "lu_nr_compat"}


def test_main_refuses_a_host_without_tpu(smoke, capsys):
    assert smoke.main() == 1
    assert '"ok"' not in capsys.readouterr().out
