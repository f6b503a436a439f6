"""``unstack_cohort``: the cohort trainer's stacked output cut into one
tree per client in one jitted dispatch. Slicing is exact, so every tree,
every uplink and every flush must be bit-identical to the eager per-leaf
slicing it replaced (``_eager_rows`` below is that old loop's slice)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import messages
from repro.core.flocora import FLoCoRAConfig, RankSchedule
from repro.core.lora import LoRAConfig, linear_apply, linear_init
from repro.core.quant import DPConfig
from repro.fl import AsyncConfig, AsyncFLServer, ClientConfig, FLServer, \
    FleetTrace, LognormalLatency, ServerConfig
from repro.fl import async_engine, server
from repro.fl.client import make_cohort_trainer, pad_cohort_batches, \
    pow2_pad, stack_cohort_batches, unstack_cohort


def _eager_rows(trained):
    k = jax.tree.leaves(trained)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], trained) for i in range(k)]


def _lora_model(rank=8):
    fz, tr = linear_init(jax.random.PRNGKey(0), 16, 10, "lora",
                         LoRAConfig(rank=rank, alpha=float(rank)),
                         base_dtype=jnp.float32)
    return {"frozen": {"lin": fz},
            "train": {"lin": tr, "bias": jnp.zeros((10,))}}


def _lora_loss(frozen, train, batch):
    logits = linear_apply(frozen["lin"], train["lin"], batch["x"], 1.0,
                          jnp.float32) + train["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None],
                                         axis=1)), {}


def _lin_data(n=120, n_clients=6, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(16, 10)).astype(np.float32)
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1).astype(np.int32)
    parts = np.array_split(rng.permutation(n), n_clients)
    return [{"x": x[p], "y": y[p]} for p in parts]


def _assert_trees_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _assert_wires_equal(msgs_a, msgs_b):
    """Words, scales and zero points, byte for byte, message by message."""
    assert len(msgs_a) == len(msgs_b)
    for a, b in zip(msgs_a, msgs_b):
        wa, wb = messages.message_to_wire(a), messages.message_to_wire(b)
        assert [n for n, _ in wa] == [n for n, _ in wb]
        for (_, da), (_, db) in zip(wa, wb):
            assert sorted(da) == sorted(db)
            for key in da:
                assert np.asarray(da[key]).tobytes() \
                    == np.asarray(db[key]).tobytes(), key


# ---------------------------------------------------------------------------
# the helper alone
# ---------------------------------------------------------------------------

def _plain_cohort():
    rng = np.random.default_rng(3)
    return {"a": jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32),
            "b": {"c": jnp.asarray(rng.normal(size=(5, 7)), jnp.bfloat16),
                  "d": jnp.arange(5, dtype=jnp.int32)}}


def _padded_cohort():
    """A real cohort trainer's output for 3 clients padded to 4 rows."""
    data = _lin_data()[:3]
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1)
    model = _lora_model()
    batches, n_steps = stack_cohort_batches(np.random.default_rng(0), data,
                                            ccfg)
    batches, n_steps = pad_cohort_batches(batches, n_steps, pow2_pad(3))
    trained, _ = make_cohort_trainer(_lora_loss, ccfg)(
        model["frozen"], model["train"], jax.tree.map(jnp.asarray, batches),
        jnp.asarray(n_steps))
    return trained, 3


@pytest.mark.parametrize("make", [lambda: (_plain_cohort(), 5),
                                  _padded_cohort],
                         ids=["plain", "pow2_padded"])
def test_unstack_cohort_rows_equal_eager_slices(make):
    trained, live = make()
    rows = unstack_cohort(trained)
    k = jax.tree.leaves(trained)[0].shape[0]
    assert len(rows) == k >= live
    for got, want in zip(rows[:live], _eager_rows(trained)[:live]):
        _assert_trees_equal(got, want)


# ---------------------------------------------------------------------------
# the sync round: uplinks identical to the eager-slice loop
# ---------------------------------------------------------------------------

SYNC_CASES = {
    "int8": {},
    "error_feedback": {"error_feedback": True},
    "dp": {"dp": DPConfig(clip_norm=1.0, noise_multiplier=0.5)},
    "mixed_rank_pow2": {"rank_schedule": RankSchedule.tiered((4, 8), 6)},
}


def _sync_server(**fkw):
    data = _lin_data()
    return FLServer(
        _lora_model(), _lora_loss, data,
        ServerConfig(rounds=2, n_clients=len(data), clients_per_round=5,
                     seed=7),
        ClientConfig(local_epochs=1, batch_size=8, lr=0.1),
        FLoCoRAConfig(rank=8, alpha=8.0, quant_bits=8, **fkw))


def _sync_uplinks(fkw):
    """Two seeded rounds: (every aggregated uplink, the rows the cohort
    programs were padded by, the final global tree)."""
    srv = _sync_server(**fkw)
    sent, padded = [], []
    agg, train = srv.aggregator.aggregate, srv.trainer

    def spy_aggregate(msgs, weights):
        sent.extend(msgs)
        return agg(msgs, weights)

    def spy_train(frozen, train0, batches, n_steps):
        padded.append(int(np.sum(np.asarray(n_steps) == 0)))
        return train(frozen, train0, batches, n_steps)

    srv.aggregator.aggregate, srv.trainer = spy_aggregate, spy_train
    srv.run(2)
    return sent, padded, srv.global_train


@pytest.mark.parametrize("fkw", list(SYNC_CASES.values()),
                         ids=list(SYNC_CASES))
def test_sync_uplinks_equal_eager_slice_path(fkw, monkeypatch):
    got, padded, g_got = _sync_uplinks(fkw)
    assert any(padded) == ("rank_schedule" in fkw)
    monkeypatch.setattr(server, "unstack_cohort", _eager_rows)
    want, _, g_want = _sync_uplinks(fkw)
    assert len(got) == 10
    _assert_wires_equal(got, want)
    _assert_trees_equal(g_got, g_want)


UNIFORM = ("int8", "error_feedback", "dp")


@pytest.mark.parametrize("fkw", [SYNC_CASES[n] for n in UNIFORM],
                         ids=UNIFORM)
def test_second_and_third_sync_rounds_compile_nothing(fkw,
                                                      count_compiles):
    """A uniform fleet's bucket keeps one shape, so the unstack compiles
    in the first round only. (A mixed fleet's bucket sizes move between
    pow2 steps from round to round, each a new trainer program too.)"""
    srv = _sync_server(**fkw)
    srv.run_round()
    with count_compiles() as c:
        srv.run_round()
        srv.run_round()
    assert c.count == 0


# ---------------------------------------------------------------------------
# the async engine: a padded micro-batch flushes the same messages
# ---------------------------------------------------------------------------

def _async_engine():
    data = _lin_data(n=240, n_clients=10)
    fcfg = FLoCoRAConfig(rank=8, alpha=8.0, quant_bits=8,
                         rank_schedule=RankSchedule.tiered((4, 8), 10))
    acfg = AsyncConfig(total_arrivals=12, concurrency=3, buffer_size=4,
                       microbatch_window=50.0, seed=0)
    trace = FleetTrace(seed=0, latency=LognormalLatency(
        compute_median_s=10.0, network_mbps=20.0))
    return AsyncFLServer(_lora_model(), _lora_loss, data, acfg,
                         ClientConfig(local_epochs=1, batch_size=8, lr=0.1),
                         fcfg, trace=trace)


def _async_flushes():
    srv = _async_engine()
    added, padded = [], []
    add, train = srv.aggregator.add, srv.trainer

    def spy_add(msg, n_k, staleness):
        added.append(msg)
        return add(msg, n_k, staleness)

    def spy_train(frozen, starts, batches, n_steps):
        padded.append(int(np.sum(np.asarray(n_steps) == 0)))
        return train(frozen, starts, batches, n_steps)

    srv.aggregator.add, srv.trainer = spy_add, spy_train
    hist = srv.run()
    return added, padded, hist, srv.global_train


def test_async_padded_microbatch_flushes_equal_eager_slice_path(
        monkeypatch):
    got, padded, hist, g_got = _async_flushes()
    assert any(padded), "no micro-batch was pow2-padded"
    monkeypatch.setattr(async_engine, "unstack_cohort", _eager_rows)
    want, _, hist_want, g_want = _async_flushes()
    assert len(hist) == len(hist_want) == 3
    _assert_wires_equal(got, want)
    _assert_trees_equal(g_got, g_want)
