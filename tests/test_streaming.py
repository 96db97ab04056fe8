"""Streaming ingestion tier tests (ISSUE 8, io/streaming.py +
ops/sampling.py): streaming==resident bit-identity (bin codes, mappers,
metadata, trained model text) on text and binary-cache sources,
chunk-boundary edge cases, pinned-sample determinism, unified reader
semantics, device-bagging==oracle, GOSS selection shape/scaling, and
config parsing/rejects."""
import os

import numpy as np
import pytest

import jax

from lightgbm_tpu.config import IOConfig, OverallConfig
from lightgbm_tpu.io import parser as parser_mod
from lightgbm_tpu.io import streaming
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.utils.log import LightGBMError


def _write_csv(path, n, f=5, seed=0, label_fn=None):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f)
    y = ((x[:, 0] + 0.5 * x[:, 1] > 0).astype(int)
         if label_fn is None else label_fn(x))
    with open(path, "w") as fh:
        for i in range(n):
            fh.write(",".join([str(y[i])]
                              + ["%.6f" % v for v in x[i]]) + "\n")
    return str(path)


def _load(path, **kw):
    return Dataset.load_train(IOConfig(data_filename=str(path), **kw))


def _assert_datasets_identical(res, stm):
    """Resident vs streamed Dataset: mappers, codes, metadata — bitwise."""
    assert res.num_data == stm.num_data
    assert res.num_total_features == stm.num_total_features
    assert list(res.used_feature_map.items()) == \
        list(stm.used_feature_map.items())
    assert len(res.bin_mappers) == len(stm.bin_mappers)
    for m1, m2 in zip(res.bin_mappers, stm.bin_mappers):
        assert m1.to_bytes() == m2.to_bytes()
    stm_bins = (np.asarray(stm.device_bins) if stm.bins is None
                else stm.bins)
    np.testing.assert_array_equal(res.bins, stm_bins)
    assert res.bins.dtype == stm_bins.dtype
    np.testing.assert_array_equal(res.metadata.label, stm.metadata.label)
    if res.metadata.weights is None:
        assert stm.metadata.weights is None
    else:
        np.testing.assert_array_equal(res.metadata.weights,
                                      stm.metadata.weights)
    if res.metadata.query_boundaries is None:
        assert stm.metadata.query_boundaries is None
    else:
        np.testing.assert_array_equal(res.metadata.query_boundaries,
                                      stm.metadata.query_boundaries)


def _train(ds, **params):
    cfg = OverallConfig()
    cfg.set({"objective": "binary", "num_iterations": "4",
             "num_leaves": "8", "min_data_in_leaf": "5",
             **{k: str(v) for k, v in params.items()}},
            require_data=False)
    b = GBDT()
    obj = create_objective(cfg.objective_type, cfg.objective_config)
    b.init(cfg.boosting_config, ds, obj)
    b.run_training(int(cfg.boosting_config.num_iterations), False)
    return b


def _model_text(b):
    return "".join(t.to_string() for t in b.models)


# ------------------------------------------------- streaming == resident


@pytest.mark.parametrize("n,chunk", [
    (50, 200),     # N below one chunk
    (128, 128),    # N exactly one chunk
    (300, 128),    # N above one chunk, ragged tail (300 = 2*128 + 44)
    (256, 128),    # exact multiple, no tail
])
def test_streaming_bit_identity_text(tmp_path, n, chunk):
    path = _write_csv(tmp_path / "t.csv", n)
    res = _load(path, streaming="false")
    stm = _load(path, streaming="true", ingest_chunk_rows=chunk)
    assert stm.bins is None and stm.device_bins is not None
    _assert_datasets_identical(res, stm)


def test_streaming_trained_model_text_identical(tmp_path):
    path = _write_csv(tmp_path / "t.csv", 400)
    res = _load(path, streaming="false")
    stm = _load(path, streaming="true", ingest_chunk_rows=128)
    assert _model_text(_train(res)) == _model_text(_train(stm))


def test_streaming_pinned_sample_beyond_sample_cnt(tmp_path,
                                                   monkeypatch):
    """Past SAMPLE_CNT rows the binning sample is the pinned-index draw —
    mappers (and so codes) must still match the resident loader."""
    from lightgbm_tpu.io import dataset as dataset_mod
    monkeypatch.setattr(dataset_mod, "SAMPLE_CNT", 100)
    path = _write_csv(tmp_path / "t.csv", 350)
    res = _load(path, streaming="false")
    stm = _load(path, streaming="true", ingest_chunk_rows=96)
    _assert_datasets_identical(res, stm)


def test_pinned_sample_indices_deterministic():
    a = streaming.pinned_sample_indices(1000, 7, 100)
    b = streaming.pinned_sample_indices(1000, 7, 100)
    np.testing.assert_array_equal(a, b)
    assert a.size == 100 and np.all(np.diff(a) > 0)
    # the resident loader's exact draw, single-homed
    rng = np.random.RandomState(7)
    np.testing.assert_array_equal(
        a, np.sort(rng.choice(1000, 100, replace=False)))
    assert streaming.pinned_sample_indices(50, 7, 100) is None


def test_streaming_sharded_load_matches_resident(tmp_path):
    """Multi-machine parse identity: every rank's streamed shard equals
    the resident loader's shard (same shard draw, same metadata
    partition)."""
    path = _write_csv(tmp_path / "t.csv", 240)
    for rank in range(3):
        res = Dataset.load_train(
            IOConfig(data_filename=path, streaming="false"),
            rank=rank, num_machines=3)
        stm = Dataset.load_train(
            IOConfig(data_filename=path, streaming="true",
                     ingest_chunk_rows=64),
            rank=rank, num_machines=3)
        # multi-process streamed loads keep the binned LOCAL shard
        # host-side (gbdt's global NamedSharding lift consumes it)
        assert stm.device_bins is None and stm.bins is not None
        assert res.num_data == stm.num_data
        np.testing.assert_array_equal(res.bins, stm.bins)
        np.testing.assert_array_equal(res.metadata.label,
                                      stm.metadata.label)


def test_streaming_weight_column(tmp_path):
    path = tmp_path / "w.csv"
    with open(path, "w") as f:
        f.write("lbl,f1,wgt,f2\n")
        for i in range(60):
            f.write("%d,%.3f,%.3f,%.3f\n"
                    % (i % 2, i * 0.1, 1.0 + i, 3.0 - i * 0.1))
    kw = dict(has_header=True, label_column="name:lbl",
              weight_column="name:wgt")
    res = _load(path, streaming="false", **kw)
    stm = _load(path, streaming="true", ingest_chunk_rows=16, **kw)
    _assert_datasets_identical(res, stm)
    np.testing.assert_allclose(stm.metadata.weights,
                               [1.0 + i for i in range(60)])


def test_streaming_shard_rows_dp_reduce_scatter_bit_identity(tmp_path):
    """Single-process DP (8 virtual devices): a streamed load with
    shard_rows=True places the device matrix row-sharded over the
    (data,) mesh axis, and training under the reduce_scatter ownership
    schedule reproduces the resident loader's model text exactly."""
    path = _write_csv(tmp_path / "t.csv", 640, f=6)
    res = _load(path, streaming="false")
    stm = Dataset.load_train(
        IOConfig(data_filename=path, streaming="true",
                 ingest_chunk_rows=96),
        shard_rows=True)
    assert stm.bins is None and stm.device_bins is not None
    # 640 rows divide the 8-device mesh: every device holds one [F, 80]
    # row shard (explicit NamedSharding placement, not replication)
    shards = stm.device_bins.addressable_shards
    assert len(shards) == 8
    assert all(s.data.shape == (stm.device_bins.shape[0], 80)
               for s in shards)
    _assert_datasets_identical(res, stm)
    assert _model_text(_train_dp8(res, 4)) == \
        _model_text(_train_dp8(stm, 4))


def _train_dp8(ds, iters=3):
    cfg = OverallConfig()
    cfg.set({"objective": "binary", "num_iterations": str(iters),
             "num_leaves": "8", "min_data_in_leaf": "5",
             "tree_learner": "data", "num_machines": "8",
             "dp_schedule": "reduce_scatter"}, require_data=False)
    from lightgbm_tpu.parallel import create_parallel_learner
    b = GBDT()
    obj = create_objective(cfg.objective_type, cfg.objective_config)
    b.init(cfg.boosting_config, ds, obj,
           learner=create_parallel_learner(cfg))
    b.run_training(iters, False)
    return b


def test_streaming_shard_rows_nondividing_replicates_on_learner_mesh(
        tmp_path):
    """A row count that does NOT divide the mesh must fall back to
    replication on the LEARNER's 8-device mesh (not a one-device commit,
    which the DP shard_map would reject as incompatible devices) — and
    still train identically to the resident loader."""
    path = _write_csv(tmp_path / "t.csv", 636, f=6)   # 636 % 8 != 0
    res = _load(path, streaming="false")
    stm = Dataset.load_train(
        IOConfig(data_filename=path, streaming="true",
                 ingest_chunk_rows=100),
        shard_rows=True, shard_devices=8)
    assert stm.device_bins is not None
    assert len(stm.device_bins.sharding.mesh.devices.reshape(-1)) == 8
    _assert_datasets_identical(res, stm)
    assert _model_text(_train_dp8(res)) == _model_text(_train_dp8(stm))


def test_streaming_cache_rerun_keeps_shard_rows(tmp_path):
    """The binary-cache branch must thread shard_rows/shard_devices: a
    cached rerun of a single-process DP run gets the same row-sharded
    placement (and trains) instead of a one-device commit crash."""
    path = _write_csv(tmp_path / "t.csv", 640, f=6)
    _load(path, streaming="true", is_save_binary_file=True)
    stm = Dataset.load_train(
        IOConfig(data_filename=path, streaming="true",
                 ingest_chunk_rows=128),
        shard_rows=True, shard_devices=8)          # hits the .bin branch
    assert stm.device_bins is not None
    shards = stm.device_bins.addressable_shards
    assert len(shards) == 8
    assert all(s.data.shape == (stm.device_bins.shape[0], 80)
               for s in shards)
    assert len(_train_dp8(stm).models) == 3
    os.unlink(path + ".bin")


def test_streaming_multi_process_stays_host_side(tmp_path, monkeypatch):
    """Multi-process runs that load with num_machines=1 (the
    feature-parallel learner) must NOT get a device-resident dataset:
    gbdt's host-input paths lift HOST arrays.  single_process() gates
    device residency on the process count."""
    path = _write_csv(tmp_path / "t.csv", 200)
    monkeypatch.setattr(streaming, "single_process", lambda: False)
    stm = _load(path, streaming="true", ingest_chunk_rows=64)
    assert stm.device_bins is None and stm.bins is not None
    res = _load(path, streaming="false")
    np.testing.assert_array_equal(res.bins, stm.bins)


def test_streamed_mixed_bin_packs_and_releases_device_matrix(tmp_path):
    """Mixed-bin packing on a streamed dataset reorders via one device
    gather and then RELEASES the unpacked [F, N] original (keeping both
    would double peak HBM at the scale streaming exists for); model text
    still matches the resident loader, and a second init on the consumed
    dataset fails loudly instead of crashing."""
    rng = np.random.RandomState(4)
    path = tmp_path / "m.csv"
    with open(path, "w") as f:
        for i in range(300):
            f.write("%d,%d,%d,%.6f,%.6f\n"
                    % (rng.randint(2), rng.randint(5), rng.randint(3),
                       rng.randn(), rng.randn()))
    res = _load(path, streaming="false")
    stm = _load(path, streaming="true", ingest_chunk_rows=90)
    b_stm = _train(stm)
    assert b_stm._pack_spec is not None   # narrow + wide classes present
    assert _model_text(_train(res)) == _model_text(b_stm)
    assert stm.device_bins is None and stm.device_bins_consumed
    with pytest.raises(LightGBMError):
        _train(stm)


# ------------------------------------------------------- binary caches


def test_streaming_cache_write_byte_identical(tmp_path):
    """is_save_binary_file under streaming writes the native cache through
    a pass-2 memmap — byte-identical to the resident save_binary."""
    path = _write_csv(tmp_path / "t.csv", 300)
    _load(path, streaming="false", is_save_binary_file=True)
    resident_cache = open(path + ".bin", "rb").read()
    os.unlink(path + ".bin")
    _load(path, streaming="true", ingest_chunk_rows=77,
          is_save_binary_file=True)
    assert open(path + ".bin", "rb").read() == resident_cache


def test_streaming_cache_load_bit_identity(tmp_path):
    path = _write_csv(tmp_path / "t.csv", 300)
    res = _load(path, streaming="false", is_save_binary_file=True)
    stm = _load(path, streaming="true", ingest_chunk_rows=64)  # reads .bin
    assert stm.device_bins is not None
    _assert_datasets_identical(res, stm)
    assert _model_text(_train(res)) == _model_text(_train(stm))


def test_streamed_dataset_save_binary_rejected(tmp_path):
    """A streamed dataset has no host bin matrix; a post-hoc save_binary
    must fail loudly (the cache is written during ingestion instead)."""
    path = _write_csv(tmp_path / "t.csv", 100)
    stm = _load(path, streaming="true", ingest_chunk_rows=64)
    with pytest.raises(LightGBMError):
        stm.save_binary(str(tmp_path / "out.bin"))


# ------------------------------------------------ reader unification


def test_readers_one_semantics(tmp_path):
    """read_lines is implemented ON TOP of read_line_chunks: identical
    row sets on blank lines, headers, and splitlines-only separators
    (\\f, \\v, \\u2028 are NOT row boundaries for file iteration — the
    old str.splitlines-based read_lines split on them)."""
    path = tmp_path / "zoo.txt"
    content = ("header,line\n"
               "\n"                      # first data line blank
               "1,2\fX\n"                # \f inside a row, not a boundary
               "\n"
               "3,4 5\n"            #   inside a row
               "5,6\n"
               "\n")
    with open(path, "w") as f:
        f.write(content)
    for skip in (False, True):
        lines = parser_mod.read_lines(str(path), skip_header=skip)
        chunked = [ln for ch in parser_mod.read_line_chunks(
            str(path), skip_header=skip, chunk_lines=2) for ln in ch]
        assert lines == chunked
        assert parser_mod.count_data_rows(str(path), skip_header=skip) \
            == len(lines)
    assert parser_mod.read_lines(str(path), skip_header=True) == \
        ["1,2\fX", "3,4 5", "5,6"]


# ------------------------------------------------------ device bagging


def _bag_ds():
    rng = np.random.RandomState(3)
    x = rng.randn(300, 4).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    return Dataset.from_arrays(x, y, max_bin=32)


def _bag_booster(ds, **params):
    cfg = OverallConfig()
    cfg.set({"objective": "binary", "num_leaves": "8",
             "min_data_in_leaf": "5", "bagging_fraction": "0.7",
             "bagging_freq": "2", "bagging_seed": "11",
             "bagging_device": "true", "grow_policy": "depthwise",
             **{k: str(v) for k, v in params.items()}},
            require_data=False)
    b = GBDT()
    obj = create_objective(cfg.objective_type, cfg.objective_config)
    b.init(cfg.boosting_config, ds, obj)
    return b


def test_device_bag_mask_oracle():
    """The device draw is a pure function of (seed, draw_index): one
    threefry fold_in + uniform + argsort, replayed here host-side."""
    from lightgbm_tpu.ops import sampling
    n, cnt = 257, 180
    for draw in (0, 1, 5):
        mask = np.asarray(sampling.bag_mask_for_draw(
            sampling.bag_key(11), draw, n, cnt))
        u = jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(11), draw), (n,))
        oracle = np.zeros(n, bool)
        oracle[np.argsort(np.asarray(u), kind="stable")[:cnt]] = True
        np.testing.assert_array_equal(mask, oracle)
        assert mask.sum() == cnt


def test_device_bagging_trains_and_uses_device_route():
    from lightgbm_tpu import telemetry
    ds = _bag_ds()
    telemetry.enable()
    try:
        b = _bag_booster(ds)
        assert b._bag_device
        for _ in range(4):
            b.train_one_iter(is_eval=False)
        routes = telemetry.counters()
        assert routes.get("bagging/device", 0) >= 1
        assert "bagging/host" not in routes
    finally:
        telemetry.disable()
        telemetry.reset()
    assert len(b.models) == 4


def test_device_bagging_chunk_and_pipeline_equivalence():
    """Device-bagged training is exact-identical across the per-iteration,
    fused-chunk and pipelined paths (the draw counter is the whole
    rewindable stream state)."""
    ds = _bag_ds()
    b1 = _bag_booster(ds)
    for _ in range(6):
        b1.train_one_iter(is_eval=False)
    b2 = _bag_booster(ds)
    b2.train_chunk(4)
    b2.train_chunk(4, limit=2)   # surplus rollback rewinds the counter
    assert _model_text(b1) == _model_text(b2)
    os.environ["LGBM_TPU_PIPELINE"] = "readback"
    try:
        b3 = _bag_booster(ds)
        for _ in range(6):
            b3.train_one_iter(is_eval=False)
        b3.flush_pipeline()
    finally:
        del os.environ["LGBM_TPU_PIPELINE"]
    assert _model_text(b1) == _model_text(b3)


def test_host_bagging_env_hatch():
    ds = _bag_ds()
    os.environ["LGBM_TPU_HOST_BAGGING"] = "1"
    try:
        b = _bag_booster(ds)
        assert not b._bag_device
    finally:
        del os.environ["LGBM_TPU_HOST_BAGGING"]
    b2 = _bag_booster(ds, bagging_device="false")
    assert not b2._bag_device
    # auto on CPU keeps the historical host draw
    b3 = _bag_booster(ds, bagging_device="auto")
    assert not b3._bag_device


def test_bagging_device_true_falls_back_per_query():
    """Per-query bagging draws are a host loop — bagging_device=true
    warns and keeps the host path instead of mis-drawing."""
    rng = np.random.RandomState(0)
    x = rng.randn(90, 4).astype(np.float32)
    y = rng.randint(0, 3, 90).astype(np.float32)
    ds = Dataset.from_arrays(x, y, max_bin=16)
    ds.metadata.query_boundaries = np.array([0, 30, 60, 90])
    cfg = OverallConfig()
    cfg.set({"objective": "lambdarank", "num_leaves": "4",
             "min_data_in_leaf": "2", "bagging_fraction": "0.5",
             "bagging_freq": "1", "bagging_device": "true"},
            require_data=False)
    b = GBDT()
    obj = create_objective(cfg.objective_type, cfg.objective_config)
    b.init(cfg.boosting_config, ds, obj)
    assert not b._bag_device


# ----------------------------------------------------------------- GOSS


def test_goss_select_shape_and_scaling():
    """Top rows kept unamplified; sampled remainder amplified on BOTH
    gradients and hessians; mask has exactly top+other rows."""
    from lightgbm_tpu.ops import sampling
    rng = np.random.RandomState(5)
    n = 200
    grad = rng.randn(1, n).astype(np.float32)
    hess = np.abs(rng.randn(1, n)).astype(np.float32)
    top_cnt, other_cnt, amp = sampling.goss_counts(n, 0.2, 0.1)
    assert (top_cnt, other_cnt) == (40, 20)
    assert amp == pytest.approx(8.0)
    g, h, mask = sampling.goss_select(
        jax.random.PRNGKey(0), grad, hess, top_cnt, other_cnt, amp)
    g, h, mask = np.asarray(g), np.asarray(h), np.asarray(mask)
    assert mask.sum() == top_cnt + other_cnt
    order = np.argsort(-np.abs(grad[0]), kind="stable")
    top = order[:top_cnt]
    assert mask[top].all()
    # top rows keep raw values; selected non-top rows carry the amp
    np.testing.assert_allclose(g[0, top], grad[0, top])
    np.testing.assert_allclose(h[0, top], hess[0, top])
    rest = np.setdiff1d(np.nonzero(mask)[0], top)
    assert rest.size == other_cnt
    np.testing.assert_allclose(g[0, rest], grad[0, rest] * amp,
                               rtol=1e-6)
    np.testing.assert_allclose(h[0, rest], hess[0, rest] * amp,
                               rtol=1e-6)


def test_goss_training_runs_and_beats_random():
    """GOSS end-to-end: trains on the per-iteration path (chunking is
    excluded), model differs from full-data training, and the train-set
    AUC anchor holds (sampled iterations still learn the signal)."""
    rng = np.random.RandomState(9)
    n = 600
    x = rng.randn(n, 5).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] + 0.3 * rng.randn(n) > 0).astype(
        np.float32)
    ds = Dataset.from_arrays(x, y, max_bin=32)

    def booster(**p):
        cfg = OverallConfig()
        cfg.set({"objective": "binary", "num_leaves": "8",
                 "min_data_in_leaf": "5", "num_iterations": "10",
                 **{k: str(v) for k, v in p.items()}},
                require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        b.init(cfg.boosting_config, ds, obj)
        # ISSUE 12 flipped the ISSUE-8 exclusion: GOSS selection is now
        # traced INSIDE the chunk programs, so goss=true keeps the fused
        # path (equivalence pinned in tests/test_goss_chunk.py)
        assert b.chunk_supported(False) if p.get("goss") else True
        b.run_training(10, False)
        return b

    b_goss = booster(goss="true", top_rate=0.2, other_rate=0.2)
    assert b_goss._goss_on and len(b_goss.models) == 10
    scores = np.asarray(b_goss.score)[0]
    # recorded-anchor style check: GOSS at (0.2, 0.2) must rank the
    # train set essentially as well as the full-data model on this
    # separable synthetic (full-data AUC here ~0.99)
    order = np.argsort(scores)
    ranks = np.empty(n); ranks[order] = np.arange(n)
    pos, neg = ranks[y == 1], ranks[y == 0]
    auc = (pos[:, None] > neg[None, :]).mean()
    assert auc > 0.95


def test_goss_deterministic_given_seed():
    rng = np.random.RandomState(2)
    x = rng.randn(300, 4).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    ds = Dataset.from_arrays(x, y, max_bin=16)

    def run():
        cfg = OverallConfig()
        cfg.set({"objective": "binary", "num_leaves": "6",
                 "min_data_in_leaf": "5", "goss": "true",
                 "bagging_seed": "17"}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        b.init(cfg.boosting_config, ds, obj)
        for _ in range(4):
            b.train_one_iter(is_eval=False)
        return _model_text(b)

    assert run() == run()


# --------------------------------------------------------------- config


def test_config_streaming_knobs():
    cfg = OverallConfig()
    cfg.set({"streaming": "true", "ingest_chunk_rows": "1000"},
            require_data=False)
    assert cfg.io_config.streaming == "true"
    assert cfg.io_config.ingest_chunk_rows == 1000
    with pytest.raises(LightGBMError):
        OverallConfig().set({"streaming": "maybe"}, require_data=False)
    with pytest.raises(LightGBMError):
        OverallConfig().set({"ingest_chunk_rows": "0"},
                            require_data=False)


def test_config_sampling_knobs():
    cfg = OverallConfig()
    cfg.set({"bagging_device": "true", "goss": "true",
             "top_rate": "0.3", "other_rate": "0.2"}, require_data=False)
    assert cfg.boosting_config.bagging_device == "true"
    assert cfg.boosting_config.goss
    assert cfg.boosting_config.top_rate == pytest.approx(0.3)
    with pytest.raises(LightGBMError):
        OverallConfig().set({"bagging_device": "sometimes"},
                            require_data=False)
    with pytest.raises(LightGBMError):
        OverallConfig().set({"goss": "true", "top_rate": "1.0"},
                            require_data=False)
    with pytest.raises(LightGBMError):
        OverallConfig().set({"goss": "true", "other_rate": "0.0"},
                            require_data=False)
    with pytest.raises(LightGBMError):
        OverallConfig().set({"goss": "true", "top_rate": "0.7",
                             "other_rate": "0.5"}, require_data=False)
    with pytest.raises(LightGBMError):
        OverallConfig().set({"goss": "true", "bagging_fraction": "0.5",
                             "bagging_freq": "1"}, require_data=False)


def test_resolve_streaming(tmp_path, monkeypatch):
    small = tmp_path / "small.csv"
    small.write_text("1,2\n")
    io = IOConfig(data_filename=str(small), streaming="auto")
    assert not streaming.resolve_streaming(io, str(small))
    monkeypatch.setattr(streaming, "AUTO_MIN_BYTES", 1)
    assert streaming.resolve_streaming(io, str(small))
    io.streaming = "false"
    assert not streaming.resolve_streaming(io, str(small))
    io.streaming = "true"
    assert streaming.resolve_streaming(io, str(small))
    io.streaming = "auto"
    assert not streaming.resolve_streaming(io, str(tmp_path / "absent"))


def test_ingest_telemetry_counters(tmp_path):
    from lightgbm_tpu import telemetry
    path = _write_csv(tmp_path / "t.csv", 200)
    telemetry.enable()
    try:
        _load(path, streaming="true", ingest_chunk_rows=64)
        c = telemetry.counters()
        assert c.get("ingest/chunks", 0) == 4     # ceil(200/64)
        assert c.get("ingest/rows", 0) == 200
        assert c.get("ingest/h2d_bytes", 0) > 0
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("devices,n,chunk", [
    (1, 1000, 300),       # one device: plain dynamic_update_slice
    (4, 1000, 300),       # chunk wider than a 250-row block, ragged tail
    (4, 4096, 512),       # chunk narrower than a block, block-aligned
    (2, 1001, 400),       # rows do not divide: replicated placement
])
def test_device_row_writer_lands_chunks_exactly(monkeypatch, devices, n,
                                                chunk):
    """The DEVICE path of DeviceRowWriter (the one a TPU takes; the CPU
    backend stages on host instead): per-chunk donated updates into a
    one-device, row-sharded or replicated buffer reproduce the matrix
    exactly, with the placement asked for."""
    from lightgbm_tpu.parallel.mesh import dataset_row_sharding
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(n + chunk)
    full = rng.randint(0, 255, (5, n)).astype(np.uint8)
    sharding = dataset_row_sharding(n, shard_rows=devices > 1,
                                    num_machines=devices)
    writer = streaming.DeviceRowWriter(5, n, np.uint8, sharding=sharding)
    for start in range(0, n, chunk):
        writer.append(full[:, start:start + chunk], start)
    out = writer.finish()
    np.testing.assert_array_equal(np.asarray(out), full)
    assert out.sharding.is_equivalent_to(sharding, 2)
    if devices > 1 and n % devices == 0:
        assert {s.data.shape for s in out.addressable_shards} \
            == {(5, n // devices)}
