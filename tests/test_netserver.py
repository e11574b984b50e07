"""Network front door (photon_ml_tpu/serving/netserver.py) and its
satellites: dual-framing decode into the shared admission path, binary
codec round-trips, typed wire errors that never poison window-mates,
per-connection backpressure edges (oversized, slowloris, mid-request
disconnect), drain-on-close, the SLO-adaptive admission controller
(serving/adaptive.py) and the replica fleet router (serving/router.py).
The FRONT-END semantics (coalescing, tenancy, hot swap) are covered by
test_serving_frontend.py; under test here is everything between a TCP
socket and ``ServingFrontend.score``."""

import asyncio
import json
import struct

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.models import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    LogisticRegressionModel,
    MatrixFactorizationModel,
    RandomEffectModel,
)
from photon_ml_tpu.serving import (
    AdaptiveAdmission,
    AdaptiveAdmissionConfig,
    BucketLadder,
    FrontendConfig,
    NetClient,
    NetServer,
    NetServerConfig,
    ReplicaRouter,
    RouterConfig,
    ServerError,
    ServingFrontend,
    WindowedBurn,
)
from photon_ml_tpu.serving.netserver import (
    MalformedFrame,
    REQUEST_MAGIC,
    RESPONSE_MAGIC,
    dataset_from_json,
    decode_request,
    decode_response,
    encode_request,
    encode_response,
    json_payload,
    read_binary_response,
    read_http_response,
)
from photon_ml_tpu.types import TaskType

DT = jnp.float64

LADDER = dict(min_rows=8, max_rows=64)

_U4 = struct.Struct("<I")


def _dataset(rng, n=60, d=6, n_users=7, n_items=5):
    x = rng.normal(0, 1, (n, d))
    x[:, -1] = 1.0
    users = rng.integers(0, n_users, n).astype(str)
    items = rng.integers(0, n_items, n).astype(str)
    user_x = sp.csr_matrix(np.hstack(
        [rng.normal(0, 1, (n, 2)), np.ones((n, 1))]))
    return GameDataset.build(
        responses=(rng.random(n) < 0.5).astype(float),
        feature_shards={"global": sp.csr_matrix(x), "user": user_x},
        ids={"userId": users, "itemId": items})


def _game_model(rng, train):
    ds = build_random_effect_dataset(
        train, RandomEffectDataConfiguration("userId", "user"),
        intercept_col=2)
    re = RandomEffectModel.zeros_like_dataset(ds, dtype=DT)
    re = re.with_coefs([jnp.asarray(rng.normal(0, 1, np.asarray(c).shape))
                        for c in re.local_coefs])
    fe = FixedEffectModel(
        LogisticRegressionModel(Coefficients(
            jnp.asarray(rng.normal(0, 1, 6)))), "global")
    mf = MatrixFactorizationModel(
        "userId", "itemId",
        jnp.asarray(rng.normal(0, 1, (7, 3))),
        jnp.asarray(rng.normal(0, 1, (5, 3))),
        np.unique(train.id_columns["userId"].vocabulary),
        np.unique(train.id_columns["itemId"].vocabulary))
    return GameModel({"fixed": fe, "perUser": re, "mf": mf},
                     TaskType.LOGISTIC_REGRESSION)


def _frontend(rng, **cfg):
    train = _dataset(rng, n=60)
    gm = _game_model(rng, train)
    fe = ServingFrontend(
        {"default": gm}, dtype=DT, ladder=BucketLadder(**LADDER),
        config=FrontendConfig(**{"coalesce_window_s": 0.001,
                                 "max_pending": 256, **cfg}))
    return fe, gm


def _singles(seed0, k, n=1):
    return [_dataset(np.random.default_rng(seed0 + i), n=n)
            for i in range(k)]


# -- codecs ----------------------------------------------------------------


def test_binary_codec_roundtrip(rng):
    data = _dataset(rng, n=23)
    payload = encode_request(data, model="tenant-a")
    assert payload[:4] == REQUEST_MAGIC
    (n,) = _U4.unpack(payload[4:8])
    assert len(payload) == 8 + n
    out, model = decode_request(payload[8:])
    assert model == "tenant-a"
    assert out.num_rows == data.num_rows == 23
    assert sorted(out.feature_shards) == sorted(data.feature_shards)
    for name in data.feature_shards:
        a, b = data.feature_shards[name].tocsr(), out.feature_shards[name]
        np.testing.assert_array_equal(a.indptr, b.indptr)
        np.testing.assert_array_equal(a.indices, b.indices)
        assert a.data.tobytes() == np.asarray(b.data).tobytes()
    for name in data.id_columns:
        a, b = data.id_columns[name], out.id_columns[name]
        np.testing.assert_array_equal(a.codes, b.codes)
        assert list(a.vocabulary) == list(b.vocabulary)
    for field in ("responses", "offsets", "weights"):
        np.testing.assert_array_equal(getattr(data, field),
                                      getattr(out, field))


def test_binary_codec_rejects_malformed(rng):
    good = encode_request(_dataset(rng, n=9))[8:]
    # truncated payload: array reads run past the end
    with pytest.raises(MalformedFrame, match="truncated"):
        decode_request(good[:len(good) // 2])
    # trailing garbage after a complete decode
    with pytest.raises(MalformedFrame, match="trailing"):
        decode_request(good + b"\x00\x00")
    # meta is not JSON
    with pytest.raises(MalformedFrame, match="not valid JSON"):
        decode_request(_U4.pack(7) + b"notjson")
    # meta JSON but wrong schema
    meta = json.dumps({"model": "m"}).encode()
    with pytest.raises(MalformedFrame, match="meta schema"):
        decode_request(_U4.pack(len(meta)) + meta)
    # meta declares a shard whose arrays the payload doesn't carry
    bad_meta = json.dumps({"model": "m", "rows": 5,
                           "shards": [["global", 6, 10]],
                           "ids": [], "extras": []}).encode()
    with pytest.raises(MalformedFrame, match="truncated"):
        decode_request(_U4.pack(len(bad_meta)) + bad_meta)


def test_response_codec_ok_and_error():
    for dt in ("<f8", "<f4"):
        scores = np.arange(5, dtype=np.dtype(dt)) * 0.25
        frame = encode_response(scores)
        assert frame[:4] == RESPONSE_MAGIC
        out = decode_response(frame[8:])
        assert out.dtype == np.dtype(dt)
        assert out.tobytes() == scores.tobytes()
    frame = encode_response(None, ("shed", "queue full", "t-123"))
    with pytest.raises(ServerError) as ei:
        decode_response(frame[8:])
    assert ei.value.kind == "shed"
    assert ei.value.trace_id == "t-123"
    assert "queue full" in ei.value.message


def test_json_codec_roundtrip(rng):
    data = _dataset(rng, n=17)
    out, model = dataset_from_json(
        json.loads(json.dumps(json_payload(data, model="m"))))
    assert model == "m"
    assert out.num_rows == 17
    for name in data.feature_shards:
        a, b = data.feature_shards[name].tocsr(), \
            out.feature_shards[name].tocsr()
        # float repr round-trips doubles exactly
        assert a.data.tobytes() == np.asarray(b.data, np.float64).tobytes()
        np.testing.assert_array_equal(a.indices, b.indices)
        np.testing.assert_array_equal(a.indptr, b.indptr)
    for name in data.id_columns:
        a, b = data.id_columns[name], out.id_columns[name]
        np.testing.assert_array_equal(
            np.asarray(a.vocabulary)[a.codes],
            np.asarray(b.vocabulary)[b.codes])
    np.testing.assert_array_equal(data.responses, out.responses)


# -- end-to-end scoring over real sockets ----------------------------------


@pytest.mark.needs_f64
def test_wire_scores_byte_identical_both_framings(rng):
    """The acceptance contract: a framed request produces the SAME BYTES
    an in-process ``frontend.score()`` call returns — binary trivially
    (raw array bytes on the wire), HTTP because JSON float repr
    round-trips doubles exactly."""
    fe, _ = _frontend(rng)
    reqs = _singles(300, 5) + [_dataset(np.random.default_rng(399), n=20)]

    async def main():
        async with fe:
            want = [np.asarray(await fe.score(r)) for r in reqs]
            server = await NetServer(fe).start()
            try:
                async with NetClient("127.0.0.1", server.port) as c:
                    got_bin = [await c.score(r) for r in reqs]
                async with NetClient("127.0.0.1", server.port,
                                     framing="http") as c:
                    got_http = [await c.score(r) for r in reqs]
            finally:
                await server.close()
            st = server.stats()
            return want, got_bin, got_http, st

    want, got_bin, got_http, st = asyncio.run(main())
    for w, b, h in zip(want, got_bin, got_http):
        assert w.tobytes() == b.tobytes()
        assert w.tobytes() == h.tobytes()
    assert st["requests_binary"] == 6 and st["requests_http"] == 6
    assert st["responses"] == 12 and st["wire_errors"] == {}
    assert st["open_connections"] == 0


@pytest.mark.needs_f64
def test_malformed_frame_never_poisons_window_mates(rng):
    """One pipelined connection interleaves a malformed payload (honest
    frame length, garbage meta) between good frames while a SECOND
    connection scores concurrently: the bad frame gets a typed in-order
    error response, every good frame on both connections scores, and
    the per-kind error counter ticks exactly once."""
    fe, gm = _frontend(rng, coalesce_window_s=0.02)
    goods = _singles(500, 5)
    other = _dataset(np.random.default_rng(599), n=1)
    bad_payload = _U4.pack(7) + b"badmeta"
    bad_frame = REQUEST_MAGIC + _U4.pack(len(bad_payload)) + bad_payload

    telemetry.reset()
    telemetry.enable()
    try:

        async def main():
            async with fe:
                server = await NetServer(fe).start()
                try:
                    r, w = await asyncio.open_connection(
                        "127.0.0.1", server.port)
                    frames = [encode_request(d) for d in goods[:3]] \
                        + [bad_frame] \
                        + [encode_request(d) for d in goods[3:]]
                    w.write(b"".join(frames))
                    await w.drain()

                    async def mate():
                        async with NetClient("127.0.0.1",
                                             server.port) as c:
                            return await c.score(other)

                    mate_task = asyncio.ensure_future(mate())
                    got = []
                    for i in range(6):
                        if i == 3:
                            with pytest.raises(ServerError) as ei:
                                await read_binary_response(r)
                            assert ei.value.kind == "malformed"
                        else:
                            got.append(await read_binary_response(r))
                    w.close()
                    mate_scores = await mate_task
                    return got, mate_scores, server.stats()
                finally:
                    await server.close()

        got, mate_scores, st = asyncio.run(main())
        for d, s in zip(goods, got):
            np.testing.assert_allclose(s, gm.score(d),
                                       rtol=1e-10, atol=1e-10)
        np.testing.assert_allclose(mate_scores, gm.score(other),
                                   rtol=1e-10, atol=1e-10)
        assert st["wire_errors"] == {"malformed": 1}
        assert st["requests_binary"] == 7  # 5 good + 1 bad + window-mate
        assert st["responses"] == 6
        snap = telemetry.snapshot()
        assert snap["counters"]["serving.net.requests_binary"] == 7
        assert snap["counters"]["serving.net.wire_errors"] == 1
        assert snap["counters"]["serving.net.errors.malformed"] == 1
        assert snap["counters"]["serving.net.responses"] == 6
        assert snap["counters"]["serving.net.connections_opened"] == 2
    finally:
        telemetry.disable()
        telemetry.reset()


def test_binary_bad_magic_is_fatal(rng):
    """Mid-stream garbage where a frame magic should be: the stream
    position can't be trusted, so the server answers with a typed
    malformed frame and closes."""
    fe, _ = _frontend(rng)

    async def main():
        async with fe:
            server = await NetServer(fe).start()
            try:
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                # Valid magic opens the binary path; the NEXT frame's
                # magic is garbage (but not an HTTP head either).
                w.write(REQUEST_MAGIC + _U4.pack(4) + b"\x00\x00\x00\x00")
                await w.drain()
                with pytest.raises(ServerError) as ei:
                    await read_binary_response(r)  # the empty-ish frame
                assert ei.value.kind == "malformed"
                w.write(b"ZZZZ" + _U4.pack(0))
                await w.drain()
                with pytest.raises(ServerError) as ei:
                    await read_binary_response(r)
                assert ei.value.kind == "malformed"
                assert await r.read() == b""  # server closed
                return server.stats()
            finally:
                await server.close()

    st = asyncio.run(main())
    assert st["wire_errors"]["malformed"] == 2
    assert st["open_connections"] == 0


def test_oversized_frame_and_body_rejected(rng):
    fe, _ = _frontend(rng)
    cfg = NetServerConfig(max_body_bytes=4096)

    async def main():
        async with fe:
            server = await NetServer(fe, cfg).start()
            try:
                # binary: declared length over the bound -> typed
                # too_large, connection closed (payload never read)
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                w.write(REQUEST_MAGIC + _U4.pack(1 << 20))
                await w.drain()
                with pytest.raises(ServerError) as ei:
                    await read_binary_response(r)
                assert ei.value.kind == "too_large"
                assert await r.read() == b""
                w.close()
                # HTTP: Content-Length over the bound -> 413, closed
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                w.write(b"POST /score HTTP/1.1\r\n"
                        b"Content-Length: 1048576\r\n\r\n")
                await w.drain()
                status, body = await read_http_response(r)
                assert status == 413
                assert json.loads(body)["error"] == "too_large"
                assert await r.read() == b""
                w.close()
                return server.stats()
            finally:
                await server.close()

    st = asyncio.run(main())
    assert st["wire_errors"]["too_large"] == 2


def test_slowloris_header_timeout_both_framings(rng):
    fe, _ = _frontend(rng)
    cfg = NetServerConfig(header_timeout_s=0.15)

    async def main():
        async with fe:
            server = await NetServer(fe, cfg).start()
            try:
                # binary: magic arrives, the length head never does
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                w.write(REQUEST_MAGIC)
                await w.drain()
                with pytest.raises(ServerError) as ei:
                    await read_binary_response(r)
                assert ei.value.kind == "timeout"
                assert await r.read() == b""
                w.close()
                # HTTP: a first byte, then the header stalls
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                w.write(b"POST /sco")
                await w.drain()
                status, body = await read_http_response(r)
                assert status == 408
                assert json.loads(body)["error"] == "timeout"
                assert await r.read() == b""
                w.close()
                return server.stats()
            finally:
                await server.close()

    st = asyncio.run(main())
    assert st["wire_errors"]["timeout"] == 2


def test_mid_request_disconnect_counted_server_stays_up(rng):
    fe, _ = _frontend(rng)

    async def main():
        async with fe:
            server = await NetServer(fe).start()
            try:
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                # Frame head promises 100 bytes; 10 arrive, then hangup.
                w.write(REQUEST_MAGIC + _U4.pack(100) + b"x" * 10)
                await w.drain()
                w.close()
                # Wait for the handler to observe the disconnect.
                for _ in range(100):
                    if server.stats()["wire_errors"].get("disconnect"):
                        break
                    await asyncio.sleep(0.01)
                # The server is still healthy: a fresh connection gets
                # a clean /healthz.
                r2, w2 = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                w2.write(b"GET /healthz HTTP/1.1\r\n"
                         b"Connection: close\r\n\r\n")
                await w2.drain()
                status, body = await read_http_response(r2)
                w2.close()
                return status, json.loads(body), server.stats()
            finally:
                await server.close()

    status, body, st = asyncio.run(main())
    assert status == 200 and body["status"] == "ok"
    assert body["models"] == ["default"]
    assert st["wire_errors"] == {"disconnect": 1}
    assert st["open_connections"] == 0


def test_shed_and_unknown_model_typed_both_framings(rng):
    """Admission rejections and unknown tenants surface as TYPED wire
    errors (binary status byte / HTTP status), with the shed rejection
    carrying the front-end's trace id; neither closes the connection."""
    fe, _ = _frontend(rng)
    fe.max_pending = 0  # everything sheds at admission
    req = _dataset(np.random.default_rng(700), n=1)
    telemetry.reset()
    telemetry.enable(trace=True)  # tracing stamps the shed trace_id

    async def main():
        async with fe:
            server = await NetServer(fe).start()
            try:
                async with NetClient("127.0.0.1", server.port) as c:
                    with pytest.raises(ServerError) as shed_b:
                        await c.score(req)
                    with pytest.raises(ServerError) as unk_b:
                        await c.score(req, model="nope")
                async with NetClient("127.0.0.1", server.port,
                                     framing="http") as c:
                    with pytest.raises(ServerError) as shed_h:
                        await c.score(req)
                    with pytest.raises(ServerError) as unk_h:
                        await c.score(req, model="nope")
                return shed_b.value, unk_b.value, shed_h.value, \
                    unk_h.value, server.stats()
            finally:
                await server.close()

    try:
        shed_b, unk_b, shed_h, unk_h, st = asyncio.run(main())
    finally:
        telemetry.disable()
        telemetry.reset()
    assert shed_b.kind == shed_h.kind == "shed"
    assert shed_b.trace_id  # admission stamped a trace id
    assert unk_b.kind == unk_h.kind == "unknown_model"
    assert "nope" in unk_b.message
    assert st["wire_errors"] == {"shed": 2, "unknown_model": 2}
    # the connections survived their typed errors (2 requests each)
    assert st["requests_binary"] == 2 and st["requests_http"] == 2


@pytest.mark.needs_f64
def test_close_drains_inflight_request(rng):
    """The drain contract: a request already read off the socket when
    ``close()`` starts still settles through the front-end and its
    response reaches the client before the connection closes."""
    fe, gm = _frontend(rng, coalesce_window_s=0.25)
    req = _dataset(np.random.default_rng(800), n=1)

    async def main():
        async with fe:
            server = await NetServer(fe).start()
            r, w = await asyncio.open_connection("127.0.0.1", server.port)
            w.write(encode_request(req))
            await w.drain()
            await asyncio.sleep(0.05)  # frame read; window still open
            await server.close()  # must drain, not drop
            scores = await read_binary_response(r)
            assert await r.read() == b""  # then EOF
            w.close()
            return scores, server.stats()

    scores, st = asyncio.run(main())
    np.testing.assert_allclose(scores, gm.score(req),
                               rtol=1e-10, atol=1e-10)
    assert st["responses"] == 1 and st["wire_errors"] == {}


def test_http_keepalive_and_connection_close(rng):
    fe, _ = _frontend(rng)

    async def main():
        async with fe:
            server = await NetServer(fe).start()
            try:
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                for _ in range(2):  # keep-alive: two requests, one conn
                    w.write(b"GET /statz HTTP/1.1\r\n\r\n")
                    await w.drain()
                    status, body = await read_http_response(r)
                    assert status == 200
                assert json.loads(body)["net"]["requests_http"] == 2
                assert server.stats()["connections_opened"] == 1
                w.write(b"GET /healthz HTTP/1.1\r\n"
                        b"Connection: close\r\n\r\n")
                await w.drain()
                status, _ = await read_http_response(r)
                assert status == 200
                assert await r.read() == b""  # server honored close
                w.close()
                # unknown path -> 404, connection stays (keep-alive)
                r, w = await asyncio.open_connection(
                    "127.0.0.1", server.port)
                w.write(b"GET /nope HTTP/1.1\r\n\r\n")
                await w.drain()
                status, _ = await read_http_response(r)
                assert status == 404
                w.write(b"GET /healthz HTTP/1.1\r\n\r\n")
                await w.drain()
                status, _ = await read_http_response(r)
                assert status == 200
                w.close()
            finally:
                await server.close()

    asyncio.run(main())


# -- SLO-adaptive admission ------------------------------------------------


class _Knobs:
    """The two attributes the controller actuates — the rest of the
    front-end is irrelevant to the control law."""

    def __init__(self, max_pending=64, window=0.002):
        self.max_pending = max_pending
        self.coalesce_window_s = window


def test_adaptive_tighten_relax_hysteresis():
    burns = []
    fe = _Knobs()
    ctl = AdaptiveAdmission(fe, burn_fn=lambda: burns.pop(0))

    def run(*seq):
        burns.extend(seq)
        while burns:
            ctl.tick()

    # Over budget: tighten IMMEDIATELY, once per hot tick.
    run(2.0)
    assert fe.max_pending == 32
    assert fe.coalesce_window_s == pytest.approx(0.003)
    run(1.5)
    assert fe.max_pending == 16
    assert fe.coalesce_window_s == pytest.approx(0.0045)
    # Dead band: no actuation either way.
    run(0.7)
    assert fe.max_pending == 16
    # Quiet ticks accrue; relax only on the 4th CONSECUTIVE one.
    run(0.1, 0.1, 0.1)
    assert fe.max_pending == 16 and ctl.stats()["relaxes"] == 0
    run(0.1)
    assert fe.max_pending == 20  # 16 * 1.25
    assert fe.coalesce_window_s == pytest.approx(0.0045 * 0.75)
    # A dead-band tick RESETS the streak: 3 quiet + dead-band + 3 quiet
    # never relaxes; the 4th consecutive quiet tick does.
    run(0.1, 0.1, 0.1, 0.7, 0.1, 0.1, 0.1)
    assert ctl.stats()["relaxes"] == 1
    run(0.1)
    assert ctl.stats()["relaxes"] == 2
    assert fe.max_pending == 25
    # Sustained quiet converges EXACTLY to the configured baseline and
    # never overshoots it.
    run(*([None] * 40))
    assert fe.max_pending == 64
    assert fe.coalesce_window_s == pytest.approx(0.002)
    relaxes = ctl.stats()["relaxes"]
    run(*([0.0] * 8))  # at base: quiet ticks are no-ops
    assert ctl.stats()["relaxes"] == relaxes
    assert fe.max_pending == 64
    # Pending floor under sustained overload.
    run(*([5.0] * 12))
    assert fe.max_pending == 1
    assert fe.coalesce_window_s == pytest.approx(0.05)  # window cap


def test_adaptive_dry_run_and_validation():
    fe = _Knobs()
    ctl = AdaptiveAdmission(
        fe, burn_fn=lambda: 9.9,
        config=AdaptiveAdmissionConfig(apply=False))
    for _ in range(5):
        ctl.tick()
    st = ctl.stats()
    assert st["ticks"] == 5 and st["tightens"] == 5
    assert st["apply"] is False
    assert fe.max_pending == 64  # measured, never actuated
    assert fe.coalesce_window_s == 0.002
    assert st["last_burn"] == 9.9
    with pytest.raises(ValueError, match="slo_specs"):
        AdaptiveAdmission(_Knobs())  # no steering source


def test_windowed_burn_measures_per_tick():
    """Burn reflects ONLY traffic since the previous measure() — the
    controller must not steer on process-lifetime averages — and the
    worst objective wins."""
    telemetry.reset()
    telemetry.enable()
    try:
        h = telemetry.histogram("t.lat_seconds")
        wb = WindowedBurn(["p99:t.lat_seconds<=10ms",
                           "ratio:t.rej/t.adm<=0.1"])
        h.observe(0.001, n=100)  # all fast
        b = wb.measure()
        assert b is not None and b < 0.5
        assert wb.measure() is None  # no new traffic this tick
        h.observe(1.0, n=50)  # every sample blows the threshold
        assert wb.measure() > 1.0
        # Counter objectives diff the same way; the max across
        # objectives steers (latency saw nothing this tick).
        telemetry.counter("t.adm").inc(100)
        telemetry.counter("t.rej").inc(50)
        assert wb.measure() == pytest.approx(5.0)  # (50/100) / 0.1
        # Old counts never leak into the next tick's ratio.
        telemetry.counter("t.adm").inc(100)
        assert wb.measure() == pytest.approx(0.0)
    finally:
        telemetry.disable()
        telemetry.reset()


# -- replica router --------------------------------------------------------


@pytest.mark.needs_f64
def test_router_spreads_and_is_byte_transparent(rng):
    """Pipelined frames through the router fan out across replicas
    (least-pending, per-REQUEST routing) and come back in request
    order, byte-identical to a direct in-process score."""
    fe_a, gm = _frontend(rng)
    fe_b = ServingFrontend(
        {"default": gm}, dtype=DT, ladder=BucketLadder(**LADDER),
        config=FrontendConfig(coalesce_window_s=0.001, max_pending=256))
    reqs = _singles(900, 10)

    async def main():
        async with fe_a:
            async with fe_b:
                servers = [await NetServer(f).start()
                           for f in (fe_a, fe_b)]
                router = await ReplicaRouter(
                    [("127.0.0.1", s.port) for s in servers]).start()
                try:
                    r, w = await asyncio.open_connection(
                        "127.0.0.1", router.port)
                    w.write(b"".join(encode_request(d) for d in reqs))
                    await w.drain()
                    got = [await read_binary_response(r)
                           for _ in range(len(reqs))]
                    w.close()
                    return got, router.stats()
                finally:
                    await router.close()
                    for s in servers:
                        await s.close()

    got, st = asyncio.run(main())
    for d, s in zip(reqs, got):
        np.testing.assert_allclose(s, gm.score(d),
                                   rtol=1e-10, atol=1e-10)
    assert st["forwarded"] == st["returned"] == 10
    assert st["backend_errors"] == 0
    spread = [b["forwarded"] for b in st["backends"]]
    assert all(n > 0 for n in spread) and sum(spread) == 10


def test_router_cold_start_concurrent_clients_one_conn_per_backend():
    """Regression: clients racing through a cold router must not open
    duplicate connections to one backend. The connect race used to
    spawn duplicate pumps that fought over the shared reader, tore the
    response framing, and closed the live connection out from under
    every in-flight request."""

    async def main():
        conn_counts = [0, 0]
        ok = encode_response(np.ones(1, dtype=np.float64))

        def handler_for(idx):
            async def handle(reader, writer):
                conn_counts[idx] += 1
                try:
                    while True:
                        head = await reader.readexactly(8)
                        (n,) = _U4.unpack(head[4:])
                        await reader.readexactly(n)
                        writer.write(ok)
                        await writer.drain()
                except (asyncio.IncompleteReadError, ConnectionError):
                    pass
                finally:
                    # 3.12: Server.wait_closed() waits for this transport
                    writer.close()
            return handle

        backends = [await asyncio.start_server(
            handler_for(i), host="127.0.0.1", port=0) for i in range(2)]
        ports = [s.sockets[0].getsockname()[1] for s in backends]
        router = await ReplicaRouter(
            [("127.0.0.1", p) for p in ports]).start()
        frame = REQUEST_MAGIC + _U4.pack(4) + b"xxxx"
        per = 25

        async def client():
            r, w = await asyncio.open_connection(
                "127.0.0.1", router.port)
            w.write(frame * per)
            await w.drain()
            got = [await read_binary_response(r) for _ in range(per)]
            w.close()
            return got

        try:
            results = await asyncio.gather(*[client() for _ in range(8)])
            st = router.stats()
        finally:
            await router.close()
            for s in backends:
                s.close()
                await s.wait_closed()
        return results, st, conn_counts

    results, st, conn_counts = asyncio.run(main())
    assert [len(g) for g in results] == [25] * 8
    assert st["backend_errors"] == 0
    assert st["forwarded"] == st["returned"] == 200
    # The sharp assertion: one persistent connection per backend, no
    # matter how many clients raced the first pick.
    assert conn_counts == [1, 1]


def test_router_backend_death_is_typed_internal_error():
    """A backend connection that dies mid-request fails its in-flight
    requests with a typed ``internal`` frame — clients never hang —
    and the backend is retried via reconnect on the next pick."""

    async def main():
        async def eat_and_close(reader, writer):
            head = await reader.readexactly(8)
            (n,) = _U4.unpack(head[4:])
            await reader.readexactly(n)
            writer.close()  # dies without answering

        backend = await asyncio.start_server(
            eat_and_close, host="127.0.0.1", port=0)
        port = backend.sockets[0].getsockname()[1]
        router = await ReplicaRouter([("127.0.0.1", port)]).start()
        try:
            frame = REQUEST_MAGIC + _U4.pack(4) + b"xxxx"
            errs = []
            r, w = await asyncio.open_connection("127.0.0.1", router.port)
            for _ in range(2):  # second request exercises reconnect
                w.write(frame)
                await w.drain()
                try:
                    await read_binary_response(r)
                except ServerError as e:
                    errs.append(e)
            w.close()
            return errs, router.stats()
        finally:
            await router.close()
            backend.close()
            await backend.wait_closed()

    errs, st = asyncio.run(main())
    assert [e.kind for e in errs] == ["internal", "internal"]
    assert "backend connection lost" in errs[0].message
    assert st["backend_errors"] == 2 and st["forwarded"] == 2


def test_router_close_returns_with_a_client_attached():
    """``close()`` with a client still connected must return: on Python
    3.12 ``Server.wait_closed()`` waits for every connection, so the
    handlers are wound up first."""

    async def main():
        async def echo_ok(reader, writer):
            writer.close()

        backend = await asyncio.start_server(
            echo_ok, host="127.0.0.1", port=0)
        port = backend.sockets[0].getsockname()[1]
        router = await ReplicaRouter([("127.0.0.1", port)]).start()
        r, w = await asyncio.open_connection("127.0.0.1", router.port)
        await asyncio.sleep(0.05)  # the router has accepted the client
        try:
            await asyncio.wait_for(router.close(), timeout=10)
            assert await asyncio.wait_for(r.read(), timeout=10) == b""
        finally:
            w.close()
            backend.close()
            await asyncio.wait_for(backend.wait_closed(), timeout=10)

    asyncio.run(main())


def test_router_rejects_malformed_magic():
    async def main():
        async def never_called(reader, writer):
            writer.close()

        backend = await asyncio.start_server(
            never_called, host="127.0.0.1", port=0)
        port = backend.sockets[0].getsockname()[1]
        router = await ReplicaRouter(
            [("127.0.0.1", port)],
            RouterConfig(max_body_bytes=1024)).start()
        try:
            r, w = await asyncio.open_connection("127.0.0.1", router.port)
            w.write(b"GET /score HTTP/1.1\r\n\r\n")  # HTTP at the router
            await w.drain()
            with pytest.raises(ServerError) as ei:
                await read_binary_response(r)
            assert ei.value.kind == "malformed"
            assert await r.read() == b""
            w.close()
            # oversized declared frame: typed too_large, closed
            r, w = await asyncio.open_connection("127.0.0.1", router.port)
            w.write(REQUEST_MAGIC + _U4.pack(1 << 20))
            await w.drain()
            with pytest.raises(ServerError) as ei:
                await read_binary_response(r)
            assert ei.value.kind == "too_large"
            w.close()
            return router.stats()
        finally:
            await router.close()
            backend.close()
            await backend.wait_closed()

    st = asyncio.run(main())
    assert st["malformed"] == 2 and st["forwarded"] == 0
