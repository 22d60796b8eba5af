"""End-to-end MNIST-style LeNet dygraph training — driver config #1
(BASELINE.json smoke: 'MNIST LeNet dygraph runs end-to-end').

Uses a synthetic 10-class digit-like dataset (zero-egress environment: no
download), exercising the full eager stack: DataLoader → conv/pool/linear →
cross-entropy → backward → Adam → metrics.
"""

import numpy as np

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import io, metric, nn, optimizer as optim


class SyntheticDigits(io.Dataset):
    """Deterministic class-dependent patterns + noise, 28x28 grayscale."""

    def __init__(self, n=256, seed=0):
        rng = np.random.RandomState(seed)
        self.labels = rng.randint(0, 10, n)
        protos = rng.randn(10, 28, 28).astype("float32")
        self.images = (protos[self.labels]
                       + 0.3 * rng.randn(n, 28, 28).astype("float32"))

    def __getitem__(self, i):
        return self.images[i][None], np.int64(self.labels[i])

    def __len__(self):
        return len(self.labels)


class LeNet(nn.Layer):
    def __init__(self, num_classes=10):
        super().__init__()
        self.features = nn.Sequential(
            nn.Conv2D(1, 6, 3, stride=1, padding=1), nn.ReLU(),
            nn.MaxPool2D(2, 2),
            nn.Conv2D(6, 16, 5, stride=1, padding=0), nn.ReLU(),
            nn.MaxPool2D(2, 2))
        self.fc = nn.Sequential(
            nn.Linear(400, 120), nn.ReLU(),
            nn.Linear(120, 84), nn.ReLU(),
            nn.Linear(84, num_classes))

    def forward(self, x):
        x = self.features(x)
        x = paddle.flatten(x, 1)
        return self.fc(x)


def test_mnist_lenet_dygraph_e2e():
    paddle.seed(42)
    train_ds = SyntheticDigits(256)
    loader = io.DataLoader(train_ds, batch_size=64, shuffle=True,
                           num_workers=2)
    model = LeNet()
    loss_fn = nn.CrossEntropyLoss()
    opt = optim.Adam(learning_rate=1e-3, parameters=model.parameters())
    acc = metric.Accuracy()

    model.train()
    for epoch in range(4):
        for x, y in loader:
            logits = model(x)
            loss = loss_fn(logits, y)
            loss.backward()
            opt.step()
            opt.clear_grad()

    model.eval()
    acc.reset()
    with paddle.no_grad():
        for x, y in io.DataLoader(train_ds, batch_size=64):
            acc.update(acc.compute(model(x), y))
    final_acc = acc.accumulate()
    assert final_acc > 0.9, f"train accuracy too low: {final_acc}"


def test_lenet_eval_deterministic_and_save_load(tmp_path):
    paddle.seed(1)
    model = LeNet()
    model.eval()
    x = paddle.randn([4, 1, 28, 28])
    out1 = model(x).numpy()
    paddle.save(model.state_dict(), str(tmp_path / "lenet.pdparams"))
    model2 = LeNet()
    model2.set_state_dict(paddle.load(str(tmp_path / "lenet.pdparams")))
    model2.eval()
    np.testing.assert_allclose(out1, model2(x).numpy(), atol=1e-6)
