"""Executor compilation-cache correctness: a dead Program's cache entry
must never be replayed for a new Program (VERDICT r1: id(program) can be
recycled by the allocator; the fix is a process-monotonic Program.uid)."""

import io

import numpy as np

import paddle_tpu.fluid as fluid


def _build_program(scale):
    """y = scale * x as a tiny program; different scale -> different
    compiled step, same feed signature."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        c = fluid.layers.fill_constant(shape=[1], dtype="float32", value=scale)
        y = fluid.layers.elementwise_mul(x=x, y=c)
    return main, y


def test_program_uid_monotonic_and_unique():
    uids = [fluid.Program().uid for _ in range(16)]
    assert len(set(uids)) == len(uids)
    assert uids == sorted(uids)
    p = fluid.Program()
    assert p.clone().uid != p.uid


def test_dead_program_id_reuse_does_not_hit_stale_cache():
    exe = fluid.Executor(fluid.CPUPlace())
    x = np.ones((2, 4), np.float32)
    seen = []
    for i in range(6):
        scale = float(i + 1)
        main, y = _build_program(scale)
        (out,) = exe.run(main, feed={"x": x}, fetch_list=[y])
        seen.append(float(out.ravel()[0]))
        del main, y  # make the id() reusable for the next allocation
    assert seen == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]


def test_v2_parameters_reference_tar_layout():
    """to_tar emits the reference v2 model-file layout: 16-byte IIQ header
    + raw f32 member plus a <name>.protobuf ParameterConfig member
    (reference python/paddle/v2/parameters.py:306,328)."""
    import struct
    import tarfile

    import paddle_tpu.v2 as paddle

    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(3))
    y = paddle.layer.fc(input=x, size=2)
    params = paddle.parameters.create(y)

    buf = io.BytesIO()
    params.to_tar(buf)
    buf.seek(0)
    with tarfile.open(fileobj=buf, mode="r") as tar:
        names = tar.getnames()
        raw_members = [n for n in names if not n.endswith(".protobuf")]
        assert raw_members, names
        for n in raw_members:
            assert n + ".protobuf" in names
            data = tar.extractfile(n).read()
            version, vsize, count = struct.unpack("IIQ", data[:16])
            assert (version, vsize) == (0, 4)
            assert len(data) == 16 + 4 * count

    # round-trip: from_tar returns a Parameters-like object with shapes
    buf.seek(0)
    loaded = paddle.parameters.Parameters.from_tar(buf)
    for n in params.names():
        np.testing.assert_allclose(loaded.get(n), params.get(n), rtol=1e-6)
        assert loaded.get_shape(n) == params.get_shape(n)

    # init_from_tar restores values into an existing Parameters
    params2 = paddle.parameters.create(y)
    before = params.get(params.names()[0]).copy()
    params2.set(params.names()[0], np.zeros_like(before))
    buf.seek(0)
    params2.init_from_tar(buf)
    np.testing.assert_allclose(params2.get(params.names()[0]), before,
                               rtol=1e-6)


def test_v2_evaluator_payload():
    """SGD(extra_layers=[classification_error]) delivers the metric in
    event.evaluator (reference book handlers read it per iteration)."""
    import paddle_tpu.v2 as paddle

    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(4))
    lbl = paddle.layer.data(
        name="lbl", type=paddle.data_type.integer_value(3)
    )
    pred = paddle.layer.fc(
        input=x, size=3, act=paddle.activation.Softmax()
    )
    cost = paddle.layer.classification_cost(input=pred, label=lbl)
    err = paddle.evaluator.classification_error(input=pred, label=lbl)

    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(learning_rate=0.1),
        extra_layers=[err],
    )

    rng = np.random.RandomState(0)
    data = [
        (rng.randn(4).astype(np.float32), int(rng.randint(3)))
        for _ in range(32)
    ]

    payloads = []

    def handler(event):
        if isinstance(event, paddle.event.EndIteration):
            payloads.append(dict(event.evaluator))

    trainer.train(
        paddle.batch(lambda: iter(data), batch_size=8),
        num_passes=1, event_handler=handler,
    )
    assert payloads and all(err.name in p for p in payloads)
    for p in payloads:
        assert 0.0 <= p[err.name] <= 1.0

    result = trainer.test(paddle.batch(lambda: iter(data), batch_size=8))
    assert err.name in result.evaluator
    assert 0.0 <= result.evaluator[err.name] <= 1.0


def test_nce_reference_formulation():
    """NCE cost matches the reference nce_op.h math: o=sigmoid(s),
    b=k/V, true cost -log(o/(o+b)), sampled cost -log(b/(o+b))."""
    import paddle_tpu.fluid as fluid

    N, D, V, K = 5, 6, 20, 4
    rng = np.random.RandomState(1)
    xv = rng.randn(N, D).astype(np.float32)
    lv = rng.randint(0, V, (N, 1)).astype(np.int64)

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[D], dtype="float32")
        lbl = fluid.layers.data(name="lbl", shape=[1], dtype="int64")
        cost = fluid.layers.nce(
            input=x, label=lbl, num_total_classes=V, num_neg_samples=K,
        )
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    (out,) = exe.run(main, feed={"x": xv, "lbl": lv}, fetch_list=[cost])
    # sampled ids are random; verify bounds instead of exact values:
    # each of the 1 true + K sampled terms contributes >= 0, and the
    # sampled terms are bounded below by -log(b/(0+b)) = 0
    assert out.shape == (N, 1)
    assert np.all(out >= 0.0)
    # the true-class term alone is >= -log(1/(1+b)) = log(1+b) > 0 is not
    # guaranteed pointwise (o can approach 1), but the sum must be finite
    assert np.all(np.isfinite(out))


def test_executor_cache_lru_bounded_with_counters():
    """Executor._cache is a bounded LRU: a long-lived process walking
    many feed-shape buckets stays at the cap (evicting oldest), and
    hit/miss/eviction counters expose occupancy (ISSUE 2 satellite)."""
    exe = fluid.Executor(fluid.CPUPlace(), cache_capacity=3)
    main, y = _build_program(2.0)
    # 5 distinct feed signatures (batch sizes) -> 5 compiles through a
    # cap of 3: size stays bounded, 2 evictions
    for b in (1, 2, 3, 4, 5):
        (out,) = exe.run(main, feed={"x": np.ones((b, 4), np.float32)},
                         fetch_list=[y])
        assert float(out.ravel()[0]) == 2.0
    st = exe.cache_stats()
    assert st["size"] == 3 and st["capacity"] == 3
    assert st["misses"] == 5 and st["hits"] == 0 and st["evictions"] == 2

    # b=5 is resident (hit); b=1 was evicted (miss + recompile + a new
    # eviction); the re-run still computes correctly either way
    (out,) = exe.run(main, feed={"x": np.ones((5, 4), np.float32)},
                     fetch_list=[y])
    assert float(out.ravel()[0]) == 2.0
    assert exe.cache_stats()["hits"] == 1
    (out,) = exe.run(main, feed={"x": np.ones((1, 4), np.float32)},
                     fetch_list=[y])
    assert float(out.ravel()[0]) == 2.0
    st = exe.cache_stats()
    assert st["misses"] == 6 and st["evictions"] == 3 and st["size"] == 3

    # LRU recency: the b=5 hit refreshed it, so it must still be
    # resident after the b=1 insertion evicted the oldest entry
    before = exe.cache_stats()["hits"]
    exe.run(main, feed={"x": np.ones((5, 4), np.float32)}, fetch_list=[y])
    assert exe.cache_stats()["hits"] == before + 1

    exe.close()
    assert exe.cache_stats()["size"] == 0


def test_executor_cache_capacity_env_and_validation():
    import pytest

    from paddle_tpu.fluid.executor import CompileCache

    with pytest.raises(ValueError, match="capacity"):
        CompileCache(0)
    import os

    old = os.environ.get("PADDLE_TPU_EXECUTOR_CACHE_CAP")
    os.environ["PADDLE_TPU_EXECUTOR_CACHE_CAP"] = "7"
    try:
        assert CompileCache().capacity == 7
    finally:
        if old is None:
            del os.environ["PADDLE_TPU_EXECUTOR_CACHE_CAP"]
        else:
            os.environ["PADDLE_TPU_EXECUTOR_CACHE_CAP"] = old


def test_device_resident_feed_no_host_round_trip():
    """A device-resident feed must reach the step as the SAME jax array
    (no np.asarray device->host copy): that silent round trip would
    cost a device->host->device transfer on every run call."""
    import jax

    from paddle_tpu.fluid.executor import _split_lod_feed

    x = jax.numpy.ones((4, 4))
    d, lod = _split_lod_feed(x)
    assert d is x and lod is None
    # ragged tuple: device data passes through, lod normalises
    d2, lod2 = _split_lod_feed((x, [[0, 2, 4]]))
    assert d2 is x and lod2 is not None
