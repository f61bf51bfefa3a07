"""Tests for the API-coverage closure wave (reference public names from
API_COVERAGE.md; semantics per the cited reference files)."""
import io as _io
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn


class TestTopLevel:
    def test_newaxis_indexing(self):
        x = paddle.ones([3])
        assert x[:, paddle.newaxis].shape == [3, 1]

    def test_create_parameter(self):
        p = paddle.create_parameter([4, 3], "float32")
        assert p.shape == [4, 3] and not p.stop_gradient
        b = paddle.create_parameter([3], "float32", is_bias=True)
        assert float(np.abs(b.numpy()).max()) == 0

    def test_batch_reader(self):
        r = paddle.batch(lambda: iter(range(7)), 3)
        assert [len(b) for b in r()] == [3, 3, 1]
        r2 = paddle.batch(lambda: iter(range(7)), 3, drop_last=True)
        assert [len(b) for b in r2()] == [3, 3]

    def test_inplace_random_fills(self):
        x = paddle.ones([500])
        paddle.geometric_(x, 0.5)
        # reference continuous form log(u)/log1p(-p): support (0, inf),
        # values below 1 included (ADVICE r2 parity fix)
        assert x.numpy().min() > 0
        paddle.log_normal_(x)
        assert x.numpy().min() > 0
        paddle.cauchy_(x)
        assert np.isfinite(x.numpy()).all()

    def test_index_add_inplace(self):
        y = paddle.zeros([5])
        paddle.index_add_(y, paddle.to_tensor([1, 3]), 0,
                          paddle.to_tensor([1.0, 2.0]))
        np.testing.assert_allclose(y.numpy(), [0, 1, 0, 2, 0])

    def test_cast_functional_and_inplace(self):
        t = paddle.ones([2])
        assert paddle.cast(t, "int32").dtype == paddle.int32
        paddle.cast_(t, "int64")
        assert t.dtype == paddle.int32 or t.dtype == paddle.int64

    def test_dlpack_roundtrip(self):
        x = paddle.to_tensor(np.arange(6, dtype=np.float32))
        cap = paddle.to_dlpack(x)
        y = paddle.from_dlpack(cap)
        np.testing.assert_allclose(y.numpy(), x.numpy())


class TestIncubateSurface:
    def test_reexports(self):
        from paddle_tpu.incubate import (LookAhead, ModelAverage,
                                         segment_sum, softmax_mask_fuse)
        assert callable(segment_sum) and callable(softmax_mask_fuse)

    def test_graph_reindex_reference_example(self):
        from paddle_tpu.incubate import graph_reindex
        rs, rd, on = graph_reindex(
            paddle.to_tensor([0, 1, 2]),
            paddle.to_tensor([8, 9, 0, 4, 7, 6, 7]),
            paddle.to_tensor(np.array([2, 3, 2], np.int32)))
        np.testing.assert_array_equal(rs.numpy(), [3, 4, 0, 5, 6, 7, 6])
        np.testing.assert_array_equal(rd.numpy(), [0, 0, 1, 1, 1, 2, 2])
        np.testing.assert_array_equal(on.numpy(), [0, 1, 2, 8, 9, 4, 7, 6])

    def test_graph_sample_and_khop(self):
        from paddle_tpu.incubate import (graph_sample_neighbors,
                                         graph_khop_sampler)
        row = paddle.to_tensor([1, 2, 2, 0, 1])
        colptr = paddle.to_tensor([0, 2, 3, 5])
        nb, ct = graph_sample_neighbors(row, colptr,
                                        paddle.to_tensor([0, 2]))
        np.testing.assert_array_equal(ct.numpy(), [2, 2])
        es, ed, si, rn = graph_khop_sampler(row, colptr,
                                            paddle.to_tensor([0]), [2, 2])
        assert es.shape[1] == 1 and int(si.numpy()[0]) == 0
        assert int(rn.numpy()[0]) == 0

    def test_identity_loss(self):
        from paddle_tpu.incubate import identity_loss
        x = paddle.to_tensor([1.0, 3.0])
        x.stop_gradient = False
        l = identity_loss(x, "mean")
        assert float(l.numpy()) == 2.0
        l.backward()
        np.testing.assert_allclose(x.grad.numpy(), [0.5, 0.5])


class TestAudio:
    def test_load_save_info_roundtrip(self, tmp_path):
        import paddle_tpu.audio as audio
        sr = 8000
        wav = np.sin(np.linspace(0, 100, 4000)).astype(np.float32)[None]
        path = str(tmp_path / "t.wav")
        audio.save(path, paddle.to_tensor(wav), sr)
        meta = audio.info(path)
        assert meta.sample_rate == sr
        out, sr2 = audio.load(path)
        assert sr2 == sr
        np.testing.assert_allclose(out.numpy()[0], wav[0], atol=1e-3)

    def test_datasets_offline_contract(self):
        import paddle_tpu.audio as audio
        with pytest.raises(FileNotFoundError):
            audio.datasets.TESS(mode="train")
        with pytest.raises(FileNotFoundError):
            audio.datasets.ESC50(mode="train")

    def test_esc50_from_tree(self, tmp_path):
        import paddle_tpu.audio as audio
        d = tmp_path / "esc"
        d.mkdir()
        wav = (np.sin(np.linspace(0, 50, 800)) * 0.5).astype(np.float32)
        for name in ["1-100-A-0.wav", "2-100-A-3.wav", "1-101-A-7.wav"]:
            audio.save(str(d / name), paddle.to_tensor(wav[None]), 8000)
        train = audio.datasets.ESC50(mode="train", split=1,
                                     archive_dir=str(d))
        test = audio.datasets.ESC50(mode="test", split=1,
                                    archive_dir=str(d))
        assert len(train) == 1 and len(test) == 2
        sig, label = test[0]
        assert sig.ndim == 1 and label in (0, 7)


class TestMiscTrivia:
    def test_amp_supported(self):
        assert paddle.amp.is_bfloat16_supported() is True
        assert paddle.amp.is_float16_supported() is True

    def test_jit_logging_knobs(self):
        paddle.jit.set_code_level(5)
        paddle.jit.set_verbosity(3)

    def test_device_extras(self):
        assert paddle.device.get_cudnn_version() is None
        assert paddle.device.get_all_custom_device_type() == []
        s = paddle.device.Stream()
        prev = paddle.device.set_stream(s)
        assert paddle.device.current_stream() is s
        paddle.device.set_stream(prev)

    def test_profiler_extras(self):
        from paddle_tpu.profiler import SummaryView, export_protobuf
        assert SummaryView.KernelView.name == "KernelView"
        assert callable(export_protobuf("/tmp/x"))

    def test_linear_lr(self):
        from paddle_tpu.optimizer.lr import LinearLR
        sch = LinearLR(learning_rate=0.5, total_steps=4,
                       start_factor=0.25, end_factor=1.0)
        lrs = []
        for _ in range(5):
            lrs.append(float(sch()))
            sch.step()
        np.testing.assert_allclose(lrs[0], 0.125, rtol=1e-6)
        np.testing.assert_allclose(lrs[4], 0.5, rtol=1e-6)
        sch.step()
        np.testing.assert_allclose(float(sch()), 0.5, rtol=1e-6)  # clamped

    def test_calculate_gain(self):
        from paddle_tpu.nn.initializer import calculate_gain
        np.testing.assert_allclose(calculate_gain("tanh"), 5.0 / 3)
        np.testing.assert_allclose(calculate_gain("leaky_relu", 1.0), 1.0)

    def test_bilinear_initializer(self):
        from paddle_tpu.nn.initializer import Bilinear
        w = np.asarray(Bilinear()((1, 1, 4, 4), "float32"))
        # symmetric stencil, peak in the center block
        np.testing.assert_allclose(w[0, 0], w[0, 0].T, rtol=1e-6)
        assert w[0, 0, 1:3, 1:3].min() > w[0, 0, 0, 0]


class TestDistributedSurface:
    def test_strategy_sections(self):
        s = dist.Strategy()
        assert s.sharding.enable is False and s.sharding.stage == 1
        s2 = dist.Strategy({"sharding": {"enable": True, "stage": 3}})
        assert s2.sharding.stage == 3 and s2.amp.enable is False
        with pytest.raises(ValueError):
            dist.Strategy("not-a-dict")

    def test_object_collectives_single_controller(self):
        out = []
        dist.all_gather_object(out, {"k": 1})
        assert out and all(o["k"] == 1 for o in out)
        lst = [1, 2]
        dist.broadcast_object_list(lst)
        assert lst == [1, 2]

    def test_wait_and_backend(self):
        t = paddle.ones([2])
        assert dist.wait(t) is t
        assert dist.get_backend() == "xla"
        assert dist.is_available()

    def test_sharding_stage_markers(self):
        s = dist.ShardingStage3("dp")
        assert s.stage == 3 and s.mesh_dim == "dp"

    def test_entry_configs(self):
        assert dist.CountFilterEntry(5)._to_attr() == "count_filter_entry:5"
        with pytest.raises(ValueError):
            dist.ProbabilityEntry(0.0)
        e = dist.ShowClickEntry("show", "click")
        assert "show" in e._to_attr()

    def test_fleet_role_makers(self):
        rm = dist.fleet.UserDefinedRoleMaker(current_id=2, worker_num=4)
        assert rm.worker_index() == 2 and rm.worker_num() == 4
        assert rm.is_worker() and not rm.is_first_worker()
        os.environ["PADDLE_TRAINER_ID"] = "1"
        os.environ["PADDLE_TRAINERS_NUM"] = "3"
        try:
            cm = dist.fleet.PaddleCloudRoleMaker()
            assert cm.worker_index() == 1 and cm.worker_num() == 3
        finally:
            del os.environ["PADDLE_TRAINER_ID"]
            del os.environ["PADDLE_TRAINERS_NUM"]

    def test_util_file_shard(self):
        u = dist.fleet.UtilBase(
            dist.fleet.UserDefinedRoleMaker(current_id=1, worker_num=3))
        files = [f"f{i}" for i in range(8)]
        shard = u.get_file_shard(files)
        # 8 files / 3 workers -> 3,3,2; rank 1 gets files 3..5
        assert shard == ["f3", "f4", "f5"]

    def test_inmemory_dataset_pipeline(self, tmp_path):
        # two slots: one sparse id slot, one dense float slot
        p = tmp_path / "part-0.txt"
        p.write_text("2 7 9 1 0.5\n1 3 1 1.5\n3 1 2 4 1 2.5\n")
        ds = dist.InMemoryDataset()
        ds.init(batch_size=2, use_var=["ids", "dense"])
        ds.set_filelist([str(p)])
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 3
        ds.set_shuffle_seed(0)
        ds.local_shuffle()
        batches = list(ds)
        assert len(batches) == 2
        assert set(batches[0].keys()) == {"ids", "dense"}
        total = sum(b["ids"].shape[0] for b in batches)
        assert total == 3
        ds.release_memory()
        assert ds.get_memory_data_size() == 0

    def test_queue_dataset_stream(self, tmp_path):
        p = tmp_path / "q.txt"
        p.write_text("1 5 1 1.0\n1 6 1 2.0\n")
        ds = dist.QueueDataset()
        ds.init(batch_size=1, use_var=["a", "b"])
        ds.set_filelist([str(p)])
        assert [b["a"][0, 0] for b in ds] == [5, 6]

    def test_data_generator_roundtrip(self, tmp_path):
        gen_out = _io.StringIO()

        class G(dist.fleet.MultiSlotDataGenerator):
            def generate_sample(self, line):
                def reader():
                    a, b = line.split(",")
                    yield [("ids", [int(a)]), ("val", [float(b)])]
                return reader

        raw = tmp_path / "raw.txt"
        raw.write_text("3,0.5\n4,1.5\n")
        g = G()
        g.set_batch(1)
        g.run_from_files([str(raw)], gen_out)
        slot = tmp_path / "slot.txt"
        slot.write_text(gen_out.getvalue())
        ds = dist.QueueDataset()
        ds.init(batch_size=2, use_var=["ids", "val"])
        ds.set_filelist([str(slot)])
        (batch,) = list(ds)
        np.testing.assert_array_equal(batch["ids"][:, 0], [3, 4])
        np.testing.assert_allclose(batch["val"][:, 0], [0.5, 1.5])

    def test_dist_model_train_eval(self):
        from paddle_tpu.optimizer import SGD
        net = nn.Linear(4, 2)
        loss = nn.MSELoss()
        opt = SGD(learning_rate=0.1, parameters=net.parameters())
        dm = dist.to_static(net, loss=loss, optimizer=opt)
        assert dm.mode == "train"
        x = paddle.randn([8, 4])
        y = paddle.zeros([8, 2])
        l0 = float(np.asarray(dm(x, y)._value if hasattr(dm(x, y), "_value")
                              else dm(x, y)))
        for _ in range(20):
            lv = dm(x, y)
        l1 = float(np.asarray(lv._value if hasattr(lv, "_value") else lv))
        assert l1 < l0
        dm.eval()
        ev = dm(x, y)
        assert float(np.asarray(ev._value if hasattr(ev, "_value")
                                else ev)) == pytest.approx(l1, rel=0.3)
        dm.predict()
        out = dm(x)
        assert out.shape == [8, 2]

    def test_shard_dataloader_passthrough(self):
        from paddle_tpu.io import DataLoader, TensorDataset
        x = paddle.randn([8, 3])
        dl = DataLoader(TensorDataset([x]), batch_size=4)
        sharded = dist.shard_dataloader(dl)
        batches = list(sharded)
        assert len(batches) == 2


class TestSparseFFTExtras:
    def test_sparse_unary_and_linalg(self):
        import paddle_tpu.sparse as sp
        d = paddle.to_tensor(np.array([[0, 2.0], [3.0, 0]], np.float32))
        c = sp.to_sparse_coo(d, 2)
        np.testing.assert_allclose(sp.sqrt(c).to_dense().numpy(),
                                   np.sqrt(d.numpy()))
        np.testing.assert_allclose(sp.deg2rad(c).to_dense().numpy(),
                                   np.deg2rad(d.numpy()), rtol=1e-6)
        assert sp.is_same_shape(c, c)
        v = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        np.testing.assert_allclose(sp.mv(c, v).numpy(),
                                   d.numpy() @ v.numpy())
        am = sp.addmm(paddle.ones([2, 2]), c,
                      paddle.to_tensor(np.eye(2, dtype=np.float32)),
                      beta=2.0, alpha=1.0)
        np.testing.assert_allclose(am.numpy(), 2.0 + d.numpy())
        r = sp.reshape(c, [4])
        np.testing.assert_allclose(r.to_dense().numpy(),
                                   d.numpy().reshape(4))
        sl = sp.slice(c, [0], [0], [1])
        np.testing.assert_allclose(sl.to_dense().numpy(), d.numpy()[0:1])
        u, s, vv = sp.pca_lowrank(paddle.to_tensor(
            np.random.RandomState(1).randn(6, 4).astype(np.float32)), q=2)
        assert u.shape == [6, 2] and s.shape == [2]

    def test_hermitian_fft_roundtrip(self):
        x = np.random.RandomState(0).randn(4, 8).astype(np.float32)
        spec = paddle.fft.ihfft2(paddle.to_tensor(x))
        back = paddle.fft.hfft2(spec, s=[4, 8])
        np.testing.assert_allclose(back.numpy(), x, atol=1e-4)
        spec_n = paddle.fft.ihfftn(paddle.to_tensor(x))
        back_n = paddle.fft.hfftn(spec_n, s=[4, 8])
        np.testing.assert_allclose(back_n.numpy(), x, atol=1e-4)


class TestStaticExtras:
    def test_save_load_roundtrip_and_backward(self, tmp_path):
        from paddle_tpu import static
        static.enable_static()
        try:
            prog = static.Program()
            with static.program_guard(prog):
                x = static.data("x", [None, 4], "float32")
                lin = nn.Linear(4, 2)
                loss = (lin(x) ** 2).sum()
                ex = static.Executor()
                ex.run(prog, feed={"x": np.ones((2, 4), np.float32)},
                       fetch_list=[loss])
                pg = static.append_backward(loss)
                assert pg and all(g is not None for _p, g in pg)
                static.save(prog, str(tmp_path / "m"))
                w0 = lin.weight.numpy().copy()
                with paddle.no_grad():
                    lin.weight._inplace_assign(lin.weight._value * 0)
                static.load(prog, str(tmp_path / "m"))
                np.testing.assert_allclose(lin.weight.numpy(), w0)
                st = static.load_program_state(str(tmp_path / "m"))
                static.set_program_state(prog, st)
        finally:
            static.disable_static()

    def test_scopes_and_global_var(self):
        from paddle_tpu import static
        static.create_global_var([2], 1.5, "float32", name="gv2")
        assert static.global_scope().find_var("gv2") is not None
        with static.scope_guard(static.Scope()):
            assert static.global_scope().find_var("gv2") is None
        with static.name_scope("block"):
            pass
        with static.device_guard("cpu"):
            pass

    def test_auc_and_ema(self):
        from paddle_tpu import static
        a, _b, _s = static.auc(
            paddle.to_tensor(np.array([[0.3, 0.7], [0.8, 0.2],
                                       [0.4, 0.6]], np.float32)),
            paddle.to_tensor(np.array([[1], [0], [1]], np.int64)))
        assert 0.9 < float(a.numpy()) <= 1.0
        lin = nn.Linear(3, 2)
        ema = static.ExponentialMovingAverage(0.9)
        ema.register(lin.parameters())
        w0 = lin.weight.numpy().copy()
        with paddle.no_grad():
            lin.weight._inplace_assign(lin.weight._value + 1.0)
        ema.update()
        with ema.apply():
            pass  # shadow applied then restored
        np.testing.assert_allclose(lin.weight.numpy(), w0 + 1.0,
                                   rtol=1e-6)

    def test_serialize_bytes(self, tmp_path):
        from paddle_tpu import static
        data = static.serialize_program()
        meta = static.deserialize_program(data)
        assert "placeholders" in meta
        static.save_to_file(str(tmp_path / "b.bin"), b"abc")
        assert static.load_from_file(str(tmp_path / "b.bin")) == b"abc"


class TestVisionOpsDetection:
    rs = np.random.RandomState(0)

    def test_deform_conv_zero_offset_is_conv(self):
        import torch
        from paddle_tpu.vision import ops as V
        x = self.rs.randn(1, 4, 8, 8).astype(np.float32)
        w = self.rs.randn(6, 4, 3, 3).astype(np.float32)
        off = np.zeros((1, 18, 8, 8), np.float32)
        ours = V.deform_conv2d(paddle.to_tensor(x), paddle.to_tensor(off),
                               paddle.to_tensor(w), padding=1).numpy()
        ref = torch.nn.functional.conv2d(torch.tensor(x),
                                         torch.tensor(w),
                                         padding=1).numpy()
        np.testing.assert_allclose(ours, ref, rtol=1e-3, atol=1e-4)
        lay = V.DeformConv2D(4, 6, 3, padding=1)
        out = lay(paddle.to_tensor(x), paddle.to_tensor(off))
        assert out.shape == [1, 6, 8, 8]

    def test_roi_ops_oracles(self):
        from paddle_tpu.vision import ops as V
        feat = self.rs.randn(1, 3, 8, 8).astype(np.float32)
        boxes = np.array([[0.0, 0.0, 7.0, 7.0]], np.float32)
        bn = np.array([1], np.int32)
        o = V.roi_pool(paddle.to_tensor(feat), paddle.to_tensor(boxes),
                       paddle.to_tensor(bn), 1).numpy()
        np.testing.assert_allclose(o[0, :, 0, 0],
                                   feat[0].max(axis=(1, 2)), rtol=1e-5)
        ramp = np.broadcast_to(
            np.arange(8, dtype=np.float32)[None, None, None, :],
            (1, 1, 8, 8)).copy()
        out_r = V.roi_align(
            paddle.to_tensor(ramp),
            paddle.to_tensor(np.array([[1., 1., 5., 5.]], np.float32)),
            paddle.to_tensor(bn), 2, sampling_ratio=1,
            aligned=True).numpy()
        np.testing.assert_allclose(out_r[0, 0, 0], [1.5, 3.5], rtol=1e-5)
        feat_ps = np.zeros((1, 8, 6, 6), np.float32)
        for c in range(8):
            feat_ps[0, c] = c
        o = V.psroi_pool(
            paddle.to_tensor(feat_ps),
            paddle.to_tensor(np.array([[0., 0., 6., 6.]], np.float32)),
            paddle.to_tensor(bn), 2).numpy()
        np.testing.assert_allclose(
            o[0], np.arange(8, dtype=np.float32).reshape(2, 2, 2),
            rtol=1e-5)

    def test_box_coder_roundtrip(self):
        from paddle_tpu.vision import ops as V
        priors = np.array([[10., 10., 30., 30.], [5., 5., 15., 25.]],
                          np.float32)
        targets = np.array([[12., 8., 33., 28.], [4., 7., 14., 26.]],
                           np.float32)
        enc = V.box_coder(paddle.to_tensor(priors), [0.1, 0.1, 0.2, 0.2],
                          paddle.to_tensor(targets)).numpy()
        diag = enc[np.arange(2), np.arange(2)][None].transpose(1, 0, 2)
        dec = V.box_coder(paddle.to_tensor(priors), [0.1, 0.1, 0.2, 0.2],
                          paddle.to_tensor(np.ascontiguousarray(diag)),
                          code_type="decode_center_size", axis=1).numpy()
        np.testing.assert_allclose(dec[:, 0], targets, rtol=1e-4,
                                   atol=1e-3)

    def test_yolo_pipeline(self):
        from paddle_tpu.vision import ops as V
        from paddle_tpu.optimizer import Adam
        pred = self.rs.randn(2, 21, 4, 4).astype(np.float32)
        boxes, scores = V.yolo_box(
            paddle.to_tensor(pred),
            paddle.to_tensor(np.array([[64, 64], [32, 32]], np.int32)),
            anchors=[10, 13, 16, 30, 33, 23], class_num=2,
            conf_thresh=0.0, downsample_ratio=16)
        assert boxes.shape == [2, 48, 4] and scores.shape == [2, 48, 2]
        out, idx, nums = V.matrix_nms(boxes, scores, 0.3, 0.1, 20, 10,
                                      return_index=True)
        assert out.shape[1] == 6
        p = paddle.to_tensor(
            self.rs.randn(1, 21, 4, 4).astype(np.float32) * 0.1)
        p.stop_gradient = False
        opt = Adam(0.05, parameters=[p])
        l0 = None
        for _ in range(30):
            loss = V.yolo_loss(
                p, paddle.to_tensor(
                    np.array([[[0.5, 0.5, 0.3, 0.4]]], np.float32)),
                paddle.to_tensor(np.array([[1]], np.int64)),
                anchors=[10, 13, 16, 30, 33, 23], anchor_mask=[0, 1, 2],
                class_num=2, ignore_thresh=0.7,
                downsample_ratio=16).sum()
            if l0 is None:
                l0 = float(loss.numpy())
            loss.backward()
            opt.step()
            opt.clear_grad()
        assert float(loss.numpy()) < 0.7 * l0

    def test_proposals_and_fpn(self):
        from paddle_tpu.vision import ops as V
        rois = np.array([[0, 0, 10, 10], [0, 0, 100, 100],
                         [0, 0, 300, 300]], np.float32)
        multi, restore, _ = V.distribute_fpn_proposals(
            paddle.to_tensor(rois), 2, 5, 4, 224)
        assert sum(m.shape[0] for m in multi) == 3
        # restore index maps the concatenated levels back to input order
        cat = np.concatenate([m.numpy() for m in multi if m.shape[0]])
        np.testing.assert_allclose(cat[restore.numpy()[:, 0]], rois)
        sc = self.rs.rand(1, 3, 4, 4).astype(np.float32)
        bd = self.rs.randn(1, 12, 4, 4).astype(np.float32) * 0.1
        anch = self.rs.rand(48, 4).astype(np.float32) * 20
        anch[:, 2:] += anch[:, :2] + 5
        r, s2, n = V.generate_proposals(
            paddle.to_tensor(sc), paddle.to_tensor(bd),
            paddle.to_tensor(np.array([[64., 64.]], np.float32)),
            paddle.to_tensor(anch),
            paddle.to_tensor(np.full((48, 4), 0.1, np.float32)),
            pre_nms_top_n=30, post_nms_top_n=10, return_rois_num=True)
        assert r.shape[1] == 4 and int(n.numpy()[0]) == r.shape[0]
        b = r.numpy()
        assert (b[:, 2] >= b[:, 0]).all() and (b[:, 3] >= b[:, 1]).all()

    def test_prior_box(self):
        from paddle_tpu.vision import ops as V
        pb, pv = V.prior_box(
            paddle.to_tensor(np.zeros((1, 8, 4, 4), np.float32)),
            paddle.to_tensor(np.zeros((1, 3, 32, 32), np.float32)),
            min_sizes=[8.0], aspect_ratios=[1.0, 2.0], flip=True,
            clip=True)
        assert pb.shape == [4, 4, 3, 4] and pv.shape == [4, 4, 3, 4]
        assert (pb.numpy() >= 0).all() and (pb.numpy() <= 1).all()

    def test_read_file(self, tmp_path):
        from paddle_tpu.vision import ops as V
        p = tmp_path / "f.bin"
        p.write_bytes(b"\x01\x02\x03")
        t = V.read_file(str(p))
        np.testing.assert_array_equal(t.numpy(), [1, 2, 3])


class TestVisionTransformsExtra:
    rs = np.random.RandomState(0)

    def test_geometry_identities(self):
        from paddle_tpu.vision import transforms as T
        img = (self.rs.rand(3, 16, 16) * 255).astype(np.float32)
        np.testing.assert_allclose(
            T.rotate(img, 0.0, interpolation="bilinear"), img, atol=1e-3)
        r90 = T.rotate(img, 90.0, interpolation="nearest")
        np.testing.assert_allclose(
            T.rotate(r90, 90.0, interpolation="nearest"),
            T.rotate(img, 180.0, interpolation="nearest"), atol=1e-3)
        np.testing.assert_allclose(
            T.affine(img, 0.0, (0, 0), 1.0, 0.0,
                     interpolation="bilinear"), img, atol=1e-3)
        corners = [(0, 0), (15, 0), (15, 15), (0, 15)]
        np.testing.assert_allclose(
            T.perspective(img, corners, corners,
                          interpolation="bilinear"), img, atol=1e-2)

    def test_color_identities_and_classes(self):
        from paddle_tpu.vision import transforms as T
        img = (self.rs.rand(3, 12, 12) * 255).astype(np.float32)
        np.testing.assert_allclose(T.adjust_hue(img, 0.0), img, atol=1e-2)
        np.testing.assert_allclose(T.adjust_saturation(img, 1.0), img,
                                   atol=1e-3)
        np.testing.assert_allclose(T.adjust_contrast(img, 1.0), img,
                                   atol=1e-3)
        g = T.to_grayscale(img, 3)
        np.testing.assert_allclose(g[0], g[1])
        e = T.erase(img, 2, 3, 4, 5, 7.0)
        assert (e[:, 2:6, 3:8] == 7.0).all()
        for cls in [T.ColorJitter(0.2, 0.2, 0.2, 0.1), T.Grayscale(3),
                    T.Pad(2), T.RandomRotation(15),
                    T.RandomAffine(10, translate=(0.1, 0.1)),
                    T.RandomPerspective(1.0, 0.3), T.RandomErasing(1.0)]:
            assert np.asarray(cls(img)).ndim == 3

    def test_crop_pad(self):
        from paddle_tpu.vision import transforms as T
        img = (self.rs.rand(3, 16, 16) * 255).astype(np.float32)
        assert T.crop(img, 2, 3, 8, 8).shape == (3, 8, 8)
        assert T.center_crop(img, 8).shape == (3, 8, 8)
        assert T.pad(img, (1, 2, 3, 4)).shape == (3, 22, 20)


class TestModelsQuantTextExtras:
    @pytest.mark.parametrize("ctor", [
        "shufflenet_v2_x0_33", "shufflenet_v2_swish", "resnext50_64x4d"])
    def test_new_model_variants_forward(self, ctor):
        from paddle_tpu.vision import models as M
        x = paddle.randn([1, 3, 64, 64])
        m = getattr(M, ctor)(num_classes=10)
        m.eval()
        assert m(x).shape == [1, 10]

    def test_quantization_bases(self):
        from paddle_tpu.quantization import (BaseObserver, BaseQuanter,
                                             quanter)
        assert issubclass(BaseQuanter, BaseObserver)

        @quanter("MyTestQuanter")
        class _Q:
            pass
        import paddle_tpu.quantization as q
        assert q.MyTestQuanter is _Q

    def test_conll05st(self, tmp_path):
        from paddle_tpu.text import Conll05st
        p = tmp_path / "conll.txt"
        p.write_text("The DT\ncat NN\nsat VB\n\ndog NN\nran VB\n")
        ds = Conll05st(data_file=str(p))
        assert len(ds) == 2
        w, t = ds[0]
        assert len(w) == 3 and len(t) == 3
        with pytest.raises(RuntimeError):
            Conll05st()


class TestReviewFixes:
    """Regression tests for code-review findings on the API wave."""

    def test_matrix_nms_linear_decay_column_compensation(self):
        from paddle_tpu.vision import ops as V
        # 3 boxes, same class: A (best), B overlaps A, C overlaps B only
        boxes = np.array([[[0, 0, 10, 10], [0, 0, 10, 8],
                           [0, 8.01, 10, 18]]], np.float32)
        scores = np.array([[[0.9, 0.8, 0.7]]], np.float32)
        out = V.matrix_nms(paddle.to_tensor(boxes),
                           paddle.to_tensor(scores),
                           score_threshold=0.0, post_threshold=0.0,
                           nms_top_k=10, keep_top_k=10,
                           background_label=-1,
                           return_rois_num=False).numpy()
        got = sorted(round(float(s), 5) for s in out[:, 1])
        # manual matrix-nms:
        # decay(B) = (1-iou(B,A))/(1-iou_max[A]) = (1-0.8)/1 -> 0.16
        # decay(C) = min over j in {A, B}:
        #   vs A: (1 - 19.9/180)/1 = 0.889444   (C∩A = 10 x 1.99)
        #   vs B: (1 - 0)/(1 - 0.8) = 5 (clamped by the min)
        # -> 0.7 * 0.889444 = 0.622611
        want = sorted([0.9, round(0.8 * 0.2, 5),
                       round(0.7 * (1 - 19.9 / 180.0), 5)])
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_yolo_loss_ignore_thresh_active(self):
        from paddle_tpu.vision import ops as V
        rs = np.random.RandomState(0)
        p = paddle.to_tensor(rs.randn(1, 21, 4, 4).astype(np.float32))
        gtb = paddle.to_tensor(
            np.array([[[0.5, 0.5, 0.6, 0.6]]], np.float32))
        gtl = paddle.to_tensor(np.array([[1]], np.int64))
        kw = dict(anchors=[10, 13, 16, 30, 33, 23], anchor_mask=[0, 1, 2],
                  class_num=2, downsample_ratio=16)
        strict = float(V.yolo_loss(p, gtb, gtl, ignore_thresh=1.01,
                                   **kw).sum().numpy())
        lax_ = float(V.yolo_loss(p, gtb, gtl, ignore_thresh=0.0,
                                 **kw).sum().numpy())
        # ignore_thresh=0 drops every non-positive objectness term ->
        # strictly smaller loss than never-ignore
        assert lax_ < strict

    def test_adjust_brightness_preserves_uint8(self):
        from paddle_tpu.vision import transforms as T
        img = (np.random.RandomState(0).rand(3, 8, 8) * 255).astype(
            np.uint8)
        for fn in (lambda i: T.adjust_brightness(i, 1.2),
                   lambda i: T.adjust_contrast(i, 1.2),
                   lambda i: T.adjust_saturation(i, 1.2),
                   lambda i: T.adjust_hue(i, 0.1)):
            assert np.asarray(fn(img)).dtype == np.uint8

    def test_hfftn_short_s_uses_last_axes(self):
        x = np.random.RandomState(0).randn(3, 4, 8).astype(np.float32)
        spec = paddle.fft.ihfftn(paddle.to_tensor(x), s=[4, 8])
        assert spec.shape[0] == 3          # leading axis untouched
        back = paddle.fft.hfftn(spec, s=[4, 8])
        np.testing.assert_allclose(back.numpy(), x, atol=1e-4)

    def test_fpn_per_image_counts(self):
        from paddle_tpu.vision import ops as V
        rois = np.array([[0, 0, 10, 10], [0, 0, 300, 300],
                         [0, 0, 12, 12], [0, 0, 100, 100]], np.float32)
        multi, restore, nums = V.distribute_fpn_proposals(
            paddle.to_tensor(rois), 2, 5, 4, 224,
            rois_num=paddle.to_tensor(np.array([2, 2], np.int32)))
        for n in nums:
            assert n.shape == [2]          # per-image counts
        total = sum(int(n.numpy().sum()) for n in nums)
        assert total == 4

    def test_observer_isinstance_contract(self):
        from paddle_tpu.quantization import (AbsmaxObserver, BaseObserver,
                                             BaseQuanter)
        from paddle_tpu.quantization.observers import AbsmaxObserverLayer
        from paddle_tpu.quantization.quanters import (
            FakeQuanterWithAbsMaxObserver)
        assert issubclass(AbsmaxObserverLayer, BaseObserver)
        assert issubclass(FakeQuanterWithAbsMaxObserver, BaseQuanter)
        assert isinstance(AbsmaxObserverLayer(), BaseObserver)


def test_decode_jpeg_roundtrip(tmp_path):
    """vision.ops.decode_jpeg: bytes tensor -> CHW uint8 (PIL path on
    TPU hosts, reference nvjpeg kernel)."""
    pytest.importorskip("PIL")
    from PIL import Image
    from paddle_tpu.vision import ops as V
    arr = (np.linspace(0, 255, 8 * 8 * 3).reshape(8, 8, 3)
           .astype("uint8"))
    p = tmp_path / "img.jpg"
    Image.fromarray(arr).save(str(p), quality=95)
    data = V.read_file(str(p))
    img = V.decode_jpeg(data, mode="rgb")
    got = np.asarray(img.numpy())
    assert got.shape == (3, 8, 8) and got.dtype == np.uint8
    # lossy codec: coarse agreement
    assert np.abs(got.transpose(1, 2, 0).astype(int) -
                  arr.astype(int)).mean() < 16


class TestDetectionRound3:
    def test_anchor_generator_reference_geometry(self):
        """reference kernel math: base box from stride area/aspect, scaled
        by anchor_size/stride, centered at offset*(stride-1)."""
        from paddle_tpu.vision import ops as V
        x = paddle.to_tensor(np.zeros((1, 8, 2, 3), np.float32))
        anchors, variances = V.anchor_generator(
            x, anchor_sizes=[32.0], aspect_ratios=[1.0],
            stride=[16.0, 16.0], offset=0.5)
        a = np.asarray(anchors.numpy())
        v = np.asarray(variances.numpy())
        assert a.shape == (2, 3, 1, 4) and v.shape == (2, 3, 1, 4)
        # cell (0,0): center 0.5*15=7.5; base 16x16 scaled by 2 -> 32x32
        np.testing.assert_allclose(a[0, 0, 0],
                                   [7.5 - 15.5, 7.5 - 15.5,
                                    7.5 + 15.5, 7.5 + 15.5])
        # stride steps between neighbouring cells
        np.testing.assert_allclose(a[0, 1, 0] - a[0, 0, 0],
                                   [16, 0, 16, 0])
        np.testing.assert_allclose(a[1, 0, 0] - a[0, 0, 0],
                                   [0, 16, 0, 16])
        np.testing.assert_allclose(v[0, 0, 0], [0.1, 0.1, 0.2, 0.2])

    def test_multiclass_nms_per_class_then_topk(self):
        from paddle_tpu.vision import ops as V
        boxes = np.array([[[0, 0, 10, 10], [1, 1, 11, 11],
                           [20, 20, 30, 30]]], np.float32)
        scores = np.array([[[0.9, 0.8, 0.2],      # class 0
                            [0.1, 0.7, 0.6]]], np.float32)  # class 1
        out, index, nums = V.multiclass_nms(
            paddle.to_tensor(boxes), paddle.to_tensor(scores),
            score_threshold=0.3, nms_top_k=10, keep_top_k=10,
            nms_threshold=0.5, return_index=True)
        o = np.asarray(out.numpy())
        # class 0: box0 (0.9) suppresses box1; box2 below threshold
        # class 1: box1 (0.7) keeps, box2 (0.6) keeps (no overlap)
        assert int(np.asarray(nums.numpy())[0]) == 3
        assert o.shape == (3, 6)
        np.testing.assert_allclose(o[0, :2], [0, 0.9])   # best row first
        np.testing.assert_allclose(sorted(o[1:, 1].tolist()), [0.6, 0.7])
        # keep_top_k=1 truncates across classes
        out2, nums2 = V.multiclass_nms(
            paddle.to_tensor(boxes), paddle.to_tensor(scores),
            score_threshold=0.3, nms_top_k=10, keep_top_k=1,
            nms_threshold=0.5)
        assert np.asarray(out2.numpy()).shape == (1, 6)
