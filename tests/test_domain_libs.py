"""fft / signal / audio / text / vision-zoo tests."""
import math
import os

import numpy as np
import pytest

import paddle_tpu as paddle


class TestFFT:
    def test_fft_matches_numpy(self):
        x = np.random.randn(8).astype(np.float32)
        out = paddle.fft.fft(paddle.to_tensor(x))
        np.testing.assert_allclose(out.numpy(), np.fft.fft(x), rtol=1e-4,
                                   atol=1e-5)

    def test_rfft_irfft_roundtrip(self):
        x = np.random.randn(16).astype(np.float32)
        f = paddle.fft.rfft(paddle.to_tensor(x))
        back = paddle.fft.irfft(f, n=16)
        np.testing.assert_allclose(back.numpy(), x, rtol=1e-4, atol=1e-5)

    def test_fft2_and_shift(self):
        x = np.random.randn(4, 4).astype(np.float32)
        out = paddle.fft.fft2(paddle.to_tensor(x))
        np.testing.assert_allclose(out.numpy(), np.fft.fft2(x), rtol=1e-4,
                                   atol=1e-4)
        sh = paddle.fft.fftshift(paddle.to_tensor(x))
        np.testing.assert_allclose(sh.numpy(), np.fft.fftshift(x))


class TestSignal:
    def test_stft_istft_roundtrip(self):
        n = 512  # hop-aligned so every sample is covered by frames
        t = np.arange(n) / n
        x = np.sin(2 * np.pi * 50 * t).astype(np.float32)
        from paddle_tpu.audio.functional import get_window
        win = get_window("hann", 128)
        spec = paddle.signal.stft(paddle.to_tensor(x), n_fft=128,
                                  hop_length=32, window=win)
        assert spec.shape[0] == 65      # onesided bins
        back = paddle.signal.istft(spec, n_fft=128, hop_length=32,
                                   window=win, length=n)
        np.testing.assert_allclose(back.numpy(), x, atol=1e-3)

    def test_stft_peak_frequency(self):
        sr, freq = 1000, 125
        t = np.arange(sr) / sr
        x = np.sin(2 * np.pi * freq * t).astype(np.float32)
        spec = paddle.signal.stft(paddle.to_tensor(x), n_fft=256,
                                  hop_length=128)
        mag = np.abs(spec.numpy()).mean(axis=-1)
        peak_bin = mag.argmax()
        np.testing.assert_allclose(peak_bin * sr / 256, freq, atol=4)


class TestAudio:
    def test_mel_matrix_shape_and_norm(self):
        from paddle_tpu.audio.functional import compute_fbank_matrix
        fb = compute_fbank_matrix(16000, 512, n_mels=40)
        assert fb.shape == [40, 257]
        assert (fb.numpy() >= 0).all()

    def test_hz_mel_roundtrip(self):
        from paddle_tpu.audio.functional import hz_to_mel, mel_to_hz
        for hz in (100.0, 440.0, 4000.0):
            np.testing.assert_allclose(mel_to_hz(hz_to_mel(hz)), hz,
                                       rtol=1e-6)

    def test_log_mel_spectrogram_layer(self):
        from paddle_tpu.audio.features import LogMelSpectrogram
        layer = LogMelSpectrogram(sr=8000, n_fft=256, n_mels=32)
        x = paddle.to_tensor(
            np.random.randn(2, 2000).astype(np.float32))
        out = layer(x)
        assert out.shape[0] == 2 and out.shape[1] == 32
        assert np.isfinite(out.numpy()).all()

    def test_mfcc_layer(self):
        from paddle_tpu.audio.features import MFCC
        layer = MFCC(sr=8000, n_mfcc=13, n_fft=256, n_mels=32)
        x = paddle.to_tensor(np.random.randn(1, 1600).astype(np.float32))
        out = layer(x)
        assert out.shape[1] == 13

    def test_wave_io_roundtrip(self, tmp_path):
        from paddle_tpu.audio import backends
        sr = 8000
        x = (0.5 * np.sin(2 * np.pi * 440 *
                          np.arange(800) / sr)).astype(np.float32)
        path = str(tmp_path / "t.wav")
        backends.save(path, paddle.to_tensor(x[None]), sr)
        back, sr2 = backends.load(path)
        assert sr2 == sr
        np.testing.assert_allclose(back.numpy()[0], x, atol=1e-3)


class TestViterbi:
    def test_matches_brute_force(self):
        rng = np.random.default_rng(0)
        B, T, N = 2, 5, 4  # last two tags are BOS/EOS in reference style
        emis = rng.standard_normal((B, T, N)).astype(np.float32)
        trans = rng.standard_normal((N, N)).astype(np.float32)
        scores, paths = paddle.text.viterbi_decode(
            paddle.to_tensor(emis), paddle.to_tensor(trans),
            include_bos_eos_tag=False)

        # brute force over all tag sequences
        import itertools
        for b in range(B):
            best, best_path = -1e30, None
            for seq in itertools.product(range(N), repeat=T):
                s = emis[b, 0, seq[0]]
                for t in range(1, T):
                    s += trans[seq[t - 1], seq[t]] + emis[b, t, seq[t]]
                if s > best:
                    best, best_path = s, seq
            np.testing.assert_allclose(scores.numpy()[b], best, rtol=1e-5)
            assert tuple(paths.numpy()[b]) == best_path


class TestViterbiBosEos:
    def test_bos_eos_rows_match_brute_force(self):
        """Reference convention: trans row N-1 = start, row N-2 = stop."""
        rng = np.random.default_rng(1)
        B, T, N = 1, 4, 5
        emis = rng.standard_normal((B, T, N)).astype(np.float32)
        trans = rng.standard_normal((N, N)).astype(np.float32)
        scores, paths = paddle.text.viterbi_decode(
            paddle.to_tensor(emis), paddle.to_tensor(trans),
            include_bos_eos_tag=True)
        import itertools
        best, best_path = -1e30, None
        for seq in itertools.product(range(N), repeat=T):
            s = trans[N - 1, seq[0]] + emis[0, 0, seq[0]]
            for t in range(1, T):
                s += trans[seq[t - 1], seq[t]] + emis[0, t, seq[t]]
            s += trans[N - 2, seq[-1]]
            if s > best:
                best, best_path = s, seq
        np.testing.assert_allclose(scores.numpy()[0], best, rtol=1e-5)
        assert tuple(paths.numpy()[0]) == best_path


class TestTextDatasets:
    def test_uci_housing_synthetic(self):
        from paddle_tpu.text import UCIHousing
        train = UCIHousing(mode="train")
        test = UCIHousing(mode="test")
        x, y = train[0]
        assert x.shape == (13,) and y.shape == (1,)
        assert len(train) + len(test) == 506

    def test_needs_file_raises(self):
        from paddle_tpu.text import Imdb, WMT14
        with pytest.raises(RuntimeError, match="data_file"):
            Imdb()
        with pytest.raises(RuntimeError, match="data_file"):
            WMT14()


class TestVisionZoo:
    @pytest.mark.parametrize("ctor,inshape", [
        ("LeNet", (2, 1, 28, 28)),
        ("mobilenet_v2", (1, 3, 64, 64)),
    ])
    def test_models_forward(self, ctor, inshape):
        from paddle_tpu.vision import models as M
        net = getattr(M, ctor)() if ctor[0].islower() else \
            getattr(M, ctor)(num_classes=10)
        net.eval()
        x = paddle.to_tensor(
            np.random.randn(*inshape).astype(np.float32) * 0.1)
        out = net(x)
        assert out.shape[0] == inshape[0]
        assert np.isfinite(out.numpy()).all()

    def test_vgg11_tiny_forward(self):
        from paddle_tpu.vision.models import vgg11
        net = vgg11(num_classes=10)
        net.eval()
        x = paddle.to_tensor(np.random.randn(1, 3, 32, 32)
                             .astype(np.float32) * 0.1)
        out = net(x)
        assert out.shape == [1, 10]


class TestExtraZooFamilies:
    """SqueezeNet/DenseNet/ShuffleNetV2/MobileNetV3/GoogLeNet/InceptionV3
    (reference: python/paddle/vision/models/)."""

    @pytest.mark.parametrize("ctor,size", [
        ("squeezenet1_1", 64), ("densenet121", 64),
        ("shufflenet_v2_x0_25", 64), ("mobilenet_v3_small", 64),
        ("googlenet", 64), ("inception_v3", 96),
    ])
    def test_forward_shapes(self, ctor, size):
        from paddle_tpu.vision import models as M
        net = getattr(M, ctor)(num_classes=7)
        net.eval()
        x = paddle.to_tensor(np.random.RandomState(0).randn(
            2, 3, size, size).astype("float32"))
        if ctor in ("densenet121", "googlenet"):
            # the two deepest run traced, as one program: eagerly each of
            # their several hundred ops compiles alone (72 s and 49 s)
            net = paddle.jit.to_static(net)
        out = net(x)
        assert tuple(out.shape) == (2, 7)
        assert np.isfinite(out.numpy()).all()

    def test_one_train_step(self):
        from paddle_tpu.vision import models as M
        paddle.seed(0)
        net = M.shufflenet_v2_x0_25(num_classes=4)
        net.train()
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        x = paddle.to_tensor(np.random.RandomState(0).randn(
            2, 3, 64, 64).astype("float32"))
        y = paddle.to_tensor(np.array([0, 1]))
        loss = paddle.nn.functional.cross_entropy(net(x), y).mean()
        loss.backward()
        opt.step()
        assert np.isfinite(float(loss.numpy()))


def test_iterable_dataset_worker_info_sharding():
    """reference get_worker_info(): an IterableDataset can self-shard by
    worker identity; the streaming producer is worker 0 of 1, and outside
    a worker the call returns None."""
    import paddle_tpu.io as io
    assert io.get_worker_info() is None
    seen_info = []

    class Stream(io.IterableDataset):
        def __iter__(self):
            wi = io.get_worker_info()
            seen_info.append((wi.id, wi.num_workers))
            lo = wi.id
            step = wi.num_workers
            for i in range(lo, 8, step):
                yield np.asarray([float(i)], np.float32)

    loader = io.DataLoader(Stream(), batch_size=2, num_workers=2)
    vals = [np.asarray(b).ravel().tolist() for b in loader]
    flat = [v for batch in vals for v in batch]
    assert flat == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0]
    assert seen_info == [(0, 1)]
    assert io.get_worker_info() is None


def test_worker_info_non_generator_iter():
    """__iter__ that RETURNS an iterator (not a generator) runs eagerly
    when iter(dataset) is called — that must happen inside the worker so
    get_worker_info() is visible."""
    import paddle_tpu.io as io

    class DS(io.IterableDataset):
        def __iter__(self):
            wi = io.get_worker_info()
            assert wi is not None and wi.num_workers == 1
            return iter([np.asarray([float(i)], np.float32)
                         for i in range(wi.id, 4, wi.num_workers)])

    loader = io.DataLoader(DS(), batch_size=2, num_workers=2)
    flat = [v for b in loader for v in np.asarray(b).ravel().tolist()]
    assert flat == [0.0, 1.0, 2.0, 3.0]


@pytest.mark.parametrize("script", [
    "serving_quantized.py", "train_hybrid_3d.py", "train_pp_vpp_moe.py",
    "recsys_ps.py", "c_serving.py"])
def test_examples_smoke(tmp_path, script):
    """The examples/ scripts must stay runnable."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = root
    env["PADDLE_RPC_REGISTRY"] = str(tmp_path)
    env["PADDLE_JOB_ID"] = "ex_smoke"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "examples", script)],
        env=env, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, timeout=300)
    assert proc.returncode == 0, (script, proc.stdout[-1200:])


def test_prefetch_to_device_order_and_sharding():
    """prefetch_to_device keeps batch order/values, transfers ahead, and
    lands batches pre-sharded when given a NamedSharding."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu import io
    from paddle_tpu._core.tensor import Tensor

    class DS(io.Dataset):
        def __len__(self):
            return 10

        def __getitem__(self, i):
            return np.full((4,), i, np.float32)

    got = list(io.prefetch_to_device(io.DataLoader(DS(), batch_size=2),
                                     size=3))
    assert len(got) == 5
    for i, b in enumerate(got):
        v = b._value if isinstance(b, Tensor) else b
        np.testing.assert_allclose(np.asarray(v)[:, 0],
                                   [2 * i, 2 * i + 1])
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    sh = NamedSharding(mesh, P("dp"))

    class DS8(io.Dataset):
        def __len__(self):
            return 16

        def __getitem__(self, i):
            return np.full((3,), i, np.float32)

    for b in io.prefetch_to_device(io.DataLoader(DS8(), batch_size=8),
                                   size=2, sharding=sh):
        v = b._value if isinstance(b, Tensor) else b
        assert len(v.sharding.device_set) == 8
