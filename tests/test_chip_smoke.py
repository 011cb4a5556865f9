"""CPU rehearsal of ``chip_smoke.py``: the same helpers the chip run uses,
at ``llama-tiny`` size on the host. It checks paths, arguments, control
flow and the last line's contract — never the chip: on this platform the
script itself must refuse to report success.
"""

import asyncio
import importlib.util
import json
import os

import jax.numpy as jnp
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_last_line_has_exactly_the_contract_shape(smoke):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert smoke.final_line(True, device) == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )
    # Success carries nothing else, whatever the caller passes along.
    assert json.loads(smoke.final_line(True, device, error="x")) == {
        "ok": True, "device": device,
    }
    failed = json.loads(smoke.final_line(False, device, error="phase x"))
    assert failed == {"ok": False, "device": device, "error": "phase x"}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_without_a_tpu_the_script_fails_and_names_the_platform(
    smoke, capsys, argv
):
    rc = smoke.main(argv)
    assert rc != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(last)
    assert result["ok"] is False
    assert result["device"]["platform"] == "cpu"
    assert "'cpu'" in result["error"] and "not a TPU" in result["error"]


def test_provider_tpu_raises_instead_of_serving_from_the_cpu():
    from pilottai_tpu.core.config import LLMConfig
    from pilottai_tpu.engine.handler import LLMHandler

    async def boot():
        handler = LLMHandler(LLMConfig(
            model_name="llama-tiny", provider="tpu", engine_slots=2,
            engine_max_seq=64, engine_compile_cache="off",
        ))
        try:
            await handler.start()
        finally:
            await handler.stop()

    with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
        asyncio.run(boot())


def test_healed_reports_every_way_a_run_repaired_itself(smoke):
    clean = {
        "counters": {"engine.requests": 9.0, "engine.rebuilds": 0.0,
                     "engine.faults.device": 0.0},
        "gauges": {"engine.degrade_level": 0.0, "engine.mesh_plan": 0.0},
    }
    assert smoke.healed(clean, {"breaker": {
        "state": "closed", "consecutive_failures": 0, "retry_after": 0.0,
    }}) == []
    dirty = {
        "counters": {
            "engine.faults.prefill": 1.0, "engine.rebuilds.prefill_failure": 1.0,
            "engine.recovered_requests": 2.0, "engine.recovery_requeued": 2.0,
            "engine.recovery_failed": 1.0, "engine.shed.batch": 3.0,
            "engine.errors": 1.0, "engine.requests": 5.0,
        },
        "gauges": {"engine.degrade_level": 1.0, "engine.mesh_plan": 2.0},
    }
    bad = smoke.healed(dirty, {"breaker": {
        "state": "open", "consecutive_failures": 5, "retry_after": 12.0,
    }})
    # Read against a baseline, only what moved since counts.
    assert smoke.healed(dirty, None, baseline=dirty) == [
        "engine.degrade_level=1", "engine.mesh_plan=2",
    ]
    names = {b.split("=")[0] for b in bad}
    assert names == {
        "engine.faults.prefill", "engine.rebuilds.prefill_failure",
        "engine.recovered_requests", "engine.recovery_requeued",
        "engine.recovery_failed", "engine.shed.batch", "engine.errors",
        "engine.degrade_level", "engine.mesh_plan", "breaker",
    }


def test_reference_forward_agrees_with_the_engine_in_float32(smoke):
    """The reference is written independently of the engine's model code;
    in float32 with dense weights the two must agree to rounding."""
    import jax

    from pilottai_tpu.models.common import init_params
    from pilottai_tpu.models.registry import get_model_config

    cfg = get_model_config("llama-tiny").replace(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(3))
    prompt = list(range(5, 45))
    nxt = [7, 9, 11]
    ref = smoke.reference_logits(
        params, cfg, jnp.asarray(prompt + nxt[:-1], jnp.int32)
    )
    got = smoke.engine_logits(params, cfg, prompt, nxt[:-1], use_flash=False)
    assert got.shape == ref.shape == (len(prompt) + 2, cfg.vocab_size)
    assert smoke.rel_rms(got, ref) < 1e-4


def test_logits_phase_rehearsal_and_its_tolerance_bites(smoke):
    out = smoke.logits_phase(
        "llama-tiny", 2, quantize=True, dtype="bfloat16", on_tpu=False,
        tol=0.05,
    )
    assert 0.0 < out["prefill_rel_rms"] < 0.05
    assert out["native_prefill_rel_rms"] > out["prefill_rel_rms"]
    # bf16 activations cannot meet a float32-grade tolerance: the check
    # is live, not decorative.
    with pytest.raises(smoke.PhaseFailed, match="beyond"):
        smoke.logits_phase(
            "llama-tiny", 2, quantize=True, dtype="bfloat16", on_tpu=False,
            tol=1e-5,
        )


def test_serve_phase_rehearsal_through_cli_run_serve(smoke):
    argv = [
        "serve", "--model", "llama-tiny", "--provider", "cpu",
        "--quantize", "int8", "--max-seq", "512", "--kv-quantize", "int8",
        "--speculate", "4", "--slots", "4", "--chunk", "2", "--port", "0",
    ]
    info = asyncio.run(smoke.serve_phase(
        argv, "serve-tiny", expect_vocab=512, expect_tied=True,
    ))
    assert info["vocab_size"] == 512 and info["tie_embeddings"] is True
    assert info["quant"]["weight_quant"] == "int8"
    # A served shape other than the expected one fails the phase.
    with pytest.raises(smoke.PhaseFailed, match="served vocab 512"):
        asyncio.run(smoke.serve_phase(
            argv, "serve-tiny", expect_vocab=128_256, expect_tied=True,
        ))


def test_orchestrator_phase_rehearsal(smoke):
    from pilottai_tpu.train.protocol import has_checkpoint

    if not has_checkpoint():
        pytest.skip("no committed protocol-s checkpoint")
    asyncio.run(smoke.orchestrator_phase("cpu"))
