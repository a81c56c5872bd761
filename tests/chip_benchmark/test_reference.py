"""The plain references against the program at toy sizes: with the program's
compute type set to float32 the two are the same arithmetic, so every number
`check.py` compares is nought to rounding."""
import pytest

import run


@pytest.mark.parametrize("workload", ["bert_toy_f32", "resnet_toy_f32"])
def test_reference_is_the_programs_arithmetic(toy_root, workload):
    result = run.run_cell(workload, 12345, 0.2, 0, root=toy_root,
                          bench_json=toy_root + "/BENCHMARK.json",
                          require_tpu=False)
    assert result["correct"] is True, result["compared"]
    c = result["compared"]
    assert max(c[f"loss{i}_gap"][0] for i in (1, 2, 3)) < 1e-5
    assert c["grad_norm_gap"][0] < 2e-3 and c["change_norm_gap"][0] < 2e-3


@pytest.mark.parametrize("family", ["bert", "resnet"])
def test_weights_come_from_the_seed(family):
    import numpy as np
    import cells
    import toy
    from reference import steps
    cfg = toy.CONFIGS[f"{family}_toy"]
    spec = cells.load_module("reference", family).param_spec(cfg)
    a = steps.make_weights(spec, (1 << 31) + 5)
    b = steps.make_weights(spec, (1 << 31) + 5)
    c = steps.make_weights(spec, 5)
    assert all(np.array_equal(a[n], b[n]) for n in a)
    assert any(not np.array_equal(a[n], c[n]) for n in a)
    assert all(v.dtype == np.float32 for v in a.values())
