"""tools/caffe_converter.py: prototxt text parsing and layer mapping
(reference tools/caffe_converter role). A LeNet-style deploy prototxt
must convert to a bindable Symbol with the expected parameters."""
import os
import sys

import numpy as np

import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

LENET = """
name: "LeNet"  # a comment
input: "data"
input_dim: 1
input_dim: 1
input_dim: 28
input_dim: 28
layer {
  name: "conv1"
  type: "Convolution"
  bottom: "data"
  top: "conv1"
  convolution_param { num_output: 20 kernel_size: 5 stride: 1 }
}
layer {
  name: "pool1"
  type: "Pooling"
  bottom: "conv1"
  top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 }
}
layer {
  name: "bn1" type: "BatchNorm" bottom: "pool1" top: "bn1"
  batch_norm_param { eps: 0.001 }
}
layer {
  name: "scale1" type: "Scale" bottom: "bn1" top: "scale1"
  scale_param { bias_term: true }
}
layer { name: "relu1" type: "ReLU" bottom: "scale1" top: "relu1" }
layer {
  name: "ip1"
  type: "InnerProduct"
  bottom: "relu1"
  top: "ip1"
  inner_product_param { num_output: 10 }
}
layer { name: "prob" type: "Softmax" bottom: "ip1" top: "prob" }
"""


def test_parse_prototxt_structure():
    from caffe_converter import parse_prototxt

    msg = parse_prototxt(LENET)
    assert msg["name"] == "LeNet"
    assert msg["input"] == "data"
    assert msg["input_dim"] == [1, 1, 28, 28]
    layers = msg["layer"]
    assert [l["name"] for l in layers] == [
        "conv1", "pool1", "bn1", "scale1", "relu1", "ip1", "prob"]
    assert layers[0]["convolution_param"]["num_output"] == 20


def test_convert_lenet_binds_and_runs():
    from caffe_converter import convert, parse_prototxt

    net, report = convert(parse_prototxt(LENET))
    args = net.list_arguments()
    assert "conv1_weight" in args and "ip1_bias" in args
    assert "bn1_gamma" in args  # Scale folded into BatchNorm
    assert "bn1_moving_mean" in net.list_auxiliary_states()
    statuses = {name: status for name, _, status in report}
    assert statuses["scale1"] == "folded into bn1"

    ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                         data=(2, 1, 28, 28), prob_label=(2,))
    for name, arr in ex.arg_dict.items():
        if name not in ("data", "prob_label"):
            arr[:] = np.random.RandomState(0).uniform(
                -0.1, 0.1, arr.shape).astype(np.float32)
    out = ex.forward(is_train=False)[0].asnumpy()
    assert out.shape == (2, 10)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)


def test_convert_cli_writes_json(tmp_path):
    import subprocess

    proto = tmp_path / "lenet.prototxt"
    proto.write_text(LENET)
    out = tmp_path / "lenet-symbol.json"
    subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools/caffe_converter.py"),
         str(proto), str(out)],
        check=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    net = mx.sym.load(str(out))
    assert "conv1_weight" in net.list_arguments()
