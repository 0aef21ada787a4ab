"""Book test: image_classification (reference
python/paddle/fluid/tests/book/test_image_classification.py) — the CIFAR
resnet (and a VGG-style net) trained to an accuracy/loss threshold, with
a save/load_inference_model round-trip."""

import os
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu as fluid
from paddle_tpu.models import resnet, vgg


def _train(build_net, passes, lr=0.01):
    images = fluid.layers.data("pixel", [3, 32, 32])
    label = fluid.layers.data("label", [1], dtype="int64")
    predict = build_net(images)
    cost = fluid.layers.cross_entropy(predict, label)
    avg_cost = fluid.layers.mean(cost)
    acc = fluid.layers.accuracy(predict, label)
    fluid.optimizer.Adam(learning_rate=lr).minimize(avg_cost)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    reader = paddle.batch(
        paddle.reader.shuffle(paddle.dataset.cifar.train10(256), 256),
        batch_size=32)
    feeder = fluid.DataFeeder([images, label], fluid.CPUPlace())

    epoch_losses = []
    accs = []
    for _ in range(passes):
        accs, losses = [], []
        for batch in reader():
            feed = feeder.feed(batch)
            lv, av = exe.run(feed=feed, fetch_list=[avg_cost, acc])
            losses.append(float(lv))
            accs.append(float(np.asarray(av).ravel()[0]))
        epoch_losses.append(float(np.mean(losses)))
    return (exe, images, predict, epoch_losses[0], epoch_losses[-1],
            float(np.mean(accs)))


@pytest.mark.slow  # ISSUE-11 durations audit: >10 s on tier-1
def test_image_classification_resnet():
    exe, images, predict, first, last, acc = _train(
        lambda img: resnet.resnet_cifar10(img, depth=20), passes=4)
    assert last < first, (first, last)
    # ABSOLUTE threshold (round-4 review): uniform-10-class CE is
    # ln(10)=2.30; a converging run must be well under 2.0
    assert last < 2.0, (first, last)
    assert acc > 0.3, acc    # reference threshold: acc converging

    # save/load_inference_model round-trip (book test infer() path)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model")
        fluid.io.save_inference_model(path, [images.name], [predict], exe)
        probe = np.random.RandomState(0).rand(2, 3, 32, 32).astype(
            np.float32)
        scope = fluid.Scope()
        exe2 = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            prog, feeds, fetches = fluid.io.load_inference_model(path, exe2)
            out, = exe2.run(prog, feed={feeds[0]: probe},
                            fetch_list=fetches)
    out = np.asarray(out)
    assert out.shape == (2, 10)
    np.testing.assert_allclose(out.sum(1), 1.0, rtol=1e-4)  # softmax


@pytest.mark.slow  # ISSUE-11 durations audit: >10 s on tier-1
def test_image_classification_vgg():
    # epoch-MEAN losses (single-batch endpoints are too noisy for VGG at
    # this scale); last epoch must beat the first on average
    exe, images, predict, first, last, acc = _train(
        lambda img: vgg.vgg16_bn_drop(img), passes=4)
    assert last < first * 0.95, (first, last)
