"""Inference/deploy path: StableHLO export artifact, code-free predictor."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.static import InputSpec


def _mlp():
    paddle.seed(0)
    return paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.GELU(),
        paddle.nn.Dropout(0.5),  # must be inert in exported (eval) graph
        paddle.nn.Linear(16, 3),
    )


def test_save_load_roundtrip(tmp_path):
    model = _mlp()
    model.eval()  # compare against eval-mode forward (dropout inert)
    x = np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32)
    want = np.asarray(model(paddle.to_tensor(x))._data)
    prefix = str(tmp_path / "deploy" / "mlp")
    inference.save_inference_model(prefix, model, [InputSpec([4, 8], "float32", "x")])

    pred = inference.load_inference_model(prefix)
    got = pred.run(x)
    assert len(got) == 1
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)


def test_dynamic_batch(tmp_path):
    model = _mlp()
    prefix = str(tmp_path / "mlp_dyn")
    inference.save_inference_model(prefix, model,
                                   [InputSpec([None, 8], "float32", "x")])
    pred = inference.load_inference_model(prefix)
    for bs in (1, 3, 17):
        x = np.ones((bs, 8), dtype=np.float32)
        out = pred.run(x)[0]
        assert out.shape == (bs, 3)
    # same batch twice must agree (dropout exported inert)
    a = pred.run(np.ones((2, 8), np.float32))[0]
    b = pred.run(np.ones((2, 8), np.float32))[0]
    np.testing.assert_array_equal(a, b)


def test_predictor_handle_api(tmp_path):
    model = _mlp()
    prefix = str(tmp_path / "mlp_h")
    inference.save_inference_model(prefix, model, [InputSpec([2, 8], "float32", "x")])
    config = inference.Config(prefix + ".pdhlo")
    pred = inference.create_predictor(config)
    names = pred.get_input_names()
    assert names == ["x"]
    h = pred.get_input_handle("x")
    h.copy_from_cpu(np.zeros((2, 8), np.float32))
    assert pred.run_handles()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    assert out.shape == (2, 3)


def test_batchnorm_buffers_frozen_in_artifact(tmp_path):
    paddle.seed(1)
    model = paddle.nn.Sequential(paddle.nn.Linear(4, 6), paddle.nn.BatchNorm1D(6))
    # train a step so running stats are non-trivial
    model.train()
    for _ in range(3):
        model(paddle.to_tensor(np.random.default_rng(2).normal(size=(8, 4)).astype(np.float32)))
    model.eval()
    x = np.random.default_rng(3).normal(size=(5, 4)).astype(np.float32)
    want = np.asarray(model(paddle.to_tensor(x))._data)
    prefix = str(tmp_path / "bn")
    inference.save_inference_model(prefix, model, [InputSpec([5, 4], "float32")])
    got = inference.Predictor(prefix).run(x)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_artifact_loads_without_model_code(tmp_path):
    """The .pdhlo program must run even if the Layer class is unavailable."""
    model = _mlp()
    prefix = str(tmp_path / "codefree")
    inference.save_inference_model(prefix, model, [InputSpec([2, 8], "float32")])
    import subprocess, sys, os
    code = f"""
import sys; sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})
import numpy as np
from paddle_tpu import inference
pred = inference.Predictor({prefix!r})
out = pred.run(np.ones((2, 8), np.float32))[0]
assert out.shape == (2, 3)
print("CODEFREE_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env)
    assert "CODEFREE_OK" in r.stdout, r.stderr[-2000:]
