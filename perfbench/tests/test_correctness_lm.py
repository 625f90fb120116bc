"""The LM training cells' correctness check at a size the CPU holds: the
sound program passes; each fault the cell can have, planted under the
timed path, and the control (float8 matmuls) fail the cell's limits."""
import pytest

from perfbench import harness
from perfbench.tests.cpu_cell import drive

LM = "smollm-360m-async.short"


@pytest.mark.parametrize("fault", ["none", "stale", "half", "altered"])
def test_sound_run_passes_and_each_fault_fails(fault, tmp_path):
    line = drive(LM, fault, tmp_path)
    assert line["correct"] is (fault == "none"), line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"tokens_per_s.short", "setup_s"}


def test_lm_control_fails_a_limit():
    """The reference in float8 matmuls against the reference in float32."""
    from perfbench.reference import lm_async as ref
    m = harness.config("smollm-360m-async")
    m.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
    t = dict(harness.traffic("short"), seq=16, batch_per_worker=2)
    want = ref.run(5, m, t, steps=3)
    got = ref.run(5, m, t, steps=3, mode="fp8")
    numbers, _ = ref.compare(got, want)
    limits = harness.limits(LM)
    assert any(numbers[k] > limits[k] for k in limits), numbers
