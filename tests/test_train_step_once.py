"""A compiled train step traces, lowers and compiles its step ONCE.

``TrainStep`` creates the optimizer's state before its first trace
(``Optimizer.create_state``), so the first call hands the step the same
pytree, avals and placement as every later call.  For each optimizer that
runs inside ``TrainStep``, driven three steps from one start three ways:

- the eager loop (``loss.backward(); opt.step(); opt.clear_grad()``),
  which creates each slot lazily, inside its first update: the values it
  creates are recorded as they are made;
- the compiled step: ``traces == 1`` and its jit holds one program, and
  the state ``create_state`` made is the eager loop's, bit for bit;
- the compiled step with ``create_state`` switched off: the lazy path
  inside the first trace, two programs.  The one-program step matches it
  bit for bit, except where XLA builds that first program around the
  constant zero state and rounds an element of the first update another
  way (``ROUNDED``); there, and against the eager loop (its update is a
  program of its own), the three steps agree to round-off.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu import observability as obs

STEPS = 3
RTOL, ATOL = 1e-3, 1e-6     # round-off of three steps, as amplified

OPTIMIZERS = {
    "SGD": lambda ps, lr: opt.SGD(lr, parameters=ps, weight_decay=0.01),
    "Momentum": lambda ps, lr: opt.Momentum(lr, 0.9, parameters=ps,
                                            use_nesterov=True),
    "Adam": lambda ps, lr: opt.Adam(lr, parameters=ps, weight_decay=0.01),
    "AdamW": lambda ps, lr: opt.AdamW(lr, parameters=ps),
    "Adagrad": lambda ps, lr: opt.Adagrad(
        lr, parameters=ps, initial_accumulator_value=0.1),
    "Adadelta": lambda ps, lr: opt.Adadelta(lr, parameters=ps),
    "RMSProp": lambda ps, lr: opt.RMSProp(lr, parameters=ps, momentum=0.5),
    "RMSProp-centered": lambda ps, lr: opt.RMSProp(
        lr, parameters=ps, momentum=0.5, centered=True),
    "Lamb": lambda ps, lr: opt.Lamb(lr, parameters=ps),
    "Adamax": lambda ps, lr: opt.Adamax(lr, parameters=ps),
    "ASGD": lambda ps, lr: opt.ASGD(lr, batch_num=2, parameters=ps),
    "NAdam": lambda ps, lr: opt.NAdam(lr, parameters=ps),
    "RAdam": lambda ps, lr: opt.RAdam(lr, parameters=ps),
    "Rprop": lambda ps, lr: opt.Rprop(lr, parameters=ps),
}
# the lazy path's first program rounds some element of the first update
# another way (one ulp on the CPU), and three steps carry it on
ROUNDED = {"Adadelta", "NAdam", "RMSProp", "RMSProp-centered"}


def loss_fn(model, x, y):
    return F.cross_entropy(model(x), y)


def make_model(dtype="float32"):
    paddle.seed(7)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    if dtype != "float32":
        model.astype(dtype)
    return model


def twin(model):
    """A second model holding copies of ``model``'s arrays."""
    other = make_model(str(next(iter(model.parameters()))._data.dtype))
    other.load_functional_state({k: jnp.array(v, copy=True) for k, v in
                                 model.functional_state().items()})
    return other


def batches(dtype="float32", n=STEPS):
    rng = np.random.RandomState(3)
    return [(jnp.asarray(rng.randn(4, 8), dtype),
             jnp.asarray(rng.randint(0, 4, (4,)), jnp.int64))
            for _ in range(n)]


def record_creation(o):
    """Wrap ``o``'s ``_acc`` and ``_get_master`` to keep every slot's
    value as it is made, by parameter; returns that record."""
    made = {}
    acc, master = o._acc, o._get_master

    def _acc(p, name, init=None):
        fresh = name not in o._accumulators.get(o._param_key(p), {})
        out = acc(p, name, init)
        if fresh:
            made[p.name, name] = np.asarray(out)
        return out

    def _get_master(p):
        fresh = o._param_key(p) not in o._master_weights
        out = master(p)
        if fresh and out is not None:
            made[p.name, "master_weight"] = np.asarray(out)
        return out

    o._acc, o._get_master = _acc, _get_master
    return made


def held_state(model, o):
    """``o``'s slots and master weights by parameter position (the twins'
    parameters have different names)."""
    return {(i, name): np.asarray(a)
            for i, p in enumerate(model.parameters())
            for name, a in [*o._accumulators.get(o._param_key(p), {}).items(),
                            *([("master_weight",
                                o._master_weights[o._param_key(p)])]
                              if o._param_key(p) in o._master_weights
                              else [])]}


def spy_creation(model, o):
    """What ``o.create_state`` makes when the step first calls it, by
    position; returns that record (filled by the call)."""
    made = {}
    create = o.create_state

    def create_state():
        held = set(held_state(model, o))
        create()
        if not made:
            made.update((k, v) for k, v in held_state(model, o).items()
                        if k not in held)

    o.create_state = create_state
    return made


def run_eager(model, o, feed):
    losses = []
    for x, y in feed:
        loss = loss_fn(model, paddle.to_tensor(x), paddle.to_tensor(y))
        loss.backward()
        o.step()
        o.clear_grad()
        losses.append(np.asarray(loss._data))
    return losses


def run_compiled(model, o, feed):
    step = paddle.jit.train_step(model, o, loss_fn)
    losses = [np.asarray(step(x, y)._data) for x, y in feed]
    return step, losses


def follow(make_opt, dtype="float32", start=None, load=None):
    """Three runs of three steps from one start (see the module's
    docstring): {"eager", "whole", "lazy"} -> (model, optimizer, losses,
    step); "made" -> the eager loop's slots as made, by position, and
    "created" -> ``create_state``'s, by position."""
    start = start or make_model(dtype)
    feed = batches(dtype)
    runs = {}
    for way in ("eager", "whole", "lazy"):
        model = twin(start)
        o = make_opt(model.parameters())
        if load is not None:
            load(start, model, o)
        if way == "eager":
            made = record_creation(o)
            runs[way] = (model, o, run_eager(model, o, feed), None)
            pos = {p.name: i for i, p in enumerate(model.parameters())}
            runs["made"] = {(pos[k], n): v for (k, n), v in made.items()}
            continue
        if way == "whole":
            runs["created"] = spy_creation(model, o)
        else:
            o.create_state = lambda: None
        step, losses = run_compiled(model, o, feed)
        runs[way] = (model, o, losses, step)
    return runs


def assert_bitwise(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


def assert_close(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    np.testing.assert_allclose(a.astype(np.float64), b.astype(np.float64),
                               rtol=RTOL, atol=ATOL, err_msg=what)


def assert_same_training(got, want, same):
    """Losses, parameters, and every slot of an updated parameter."""
    gm, go, gl, _ = got
    wm, wo, wl, _ = want
    for i, (g, w) in enumerate(zip(gl, wl)):
        same(g, w, f"loss {i}")
    for i, (g, w) in enumerate(zip(gm.parameters(), wm.parameters())):
        same(g._data, w._data, f"parameter {i}")
    held, wanted = held_state(gm, go), held_state(wm, wo)
    for key, w in wanted.items():
        same(held[key], w, f"slot {key}")


def assert_one_program(step):
    assert step.traces == 1
    assert step._compiled._cache_size() == 1


def check(runs, bitwise=True):
    """What every case holds to."""
    assert_one_program(runs["whole"][3])
    # the slots create_state made are the lazily made ones, bit for bit
    # (the eager loop makes them for every parameter it updates: here all)
    assert set(runs["created"]) == set(runs["made"])
    for key, v in runs["made"].items():
        assert_bitwise(runs["created"][key], v, f"created {key}")
    assert_same_training(runs["whole"], runs["eager"], assert_close)
    assert_same_training(runs["whole"], runs["lazy"],
                         assert_bitwise if bitwise else assert_close)


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_one_program_and_the_lazily_made_state(name):
    runs = follow(lambda ps: OPTIMIZERS[name](ps, 0.05))
    check(runs, bitwise=name not in ROUNDED)
    if name != "SGD":           # SGD carries no slots: one program anyway
        assert runs["lazy"][3].traces == 2


def test_lbfgs_has_no_slots_to_create():
    """LBFGS steps through a closure that re-evaluates the loss, so it
    never runs inside ``TrainStep``; it has no per-parameter update and
    ``create_state`` leaves it alone."""
    model = make_model()
    o = opt.LBFGS(parameters=model.parameters())
    o.create_state()
    assert o.opt_state() == {"acc": {}, "master": {}, "step": 0}


def test_multi_precision_on_bfloat16_parameters():
    runs = follow(lambda ps: opt.AdamW(0.05, parameters=ps,
                                       multi_precision=True), "bfloat16")
    check(runs)
    masters = [k for k in runs["created"] if k[1] == "master_weight"]
    assert len(masters) == len(list(runs["whole"][0].parameters()))
    assert all(runs["created"][k].dtype == np.float32 for k in masters)


def test_grad_clip():
    check(follow(lambda ps: opt.Adam(
        0.05, parameters=ps, grad_clip=nn.ClipGradByGlobalNorm(0.01))))


def test_lr_scheduler_sets_the_created_state():
    """Rprop's per-element learning rate starts at the optimizer's lr,
    here a scheduler's: the created slot holds the scheduler's value."""
    runs = follow(lambda ps: opt.Rprop(
        opt.lr.StepDecay(0.02, step_size=10, gamma=0.5), parameters=ps))
    check(runs)
    rates = [v for k, v in runs["created"].items()
             if k[1] == "learning_rate"]
    assert len(rates) == len(list(runs["whole"][0].parameters()))
    assert all(np.all(v == np.float32(0.02)) for v in rates)


def test_a_stepped_scheduler_is_followed_by_the_one_program():
    """The lr rides into the compiled step as an argument, so a warmup
    from 0 stepped after every call (paddle's convention) leaves the
    first step's parameters put and moves them from the second on, as
    the eager loop does, in one program."""
    moved = {}
    for way in ("eager", "compiled"):
        model = make_model()
        warmup = opt.lr.LinearWarmup(0.05, warmup_steps=2, start_lr=0.0,
                                     end_lr=0.05)
        o = opt.AdamW(warmup, parameters=model.parameters())
        step = paddle.jit.train_step(model, o, loss_fn)
        before = [np.asarray(p._data) for p in model.parameters()]
        seen = []
        for x, y in batches():
            if way == "eager":
                run_eager(model, o, [(x, y)])
            else:
                step(x, y)
            warmup.step()
            seen.append([np.asarray(p._data) for p in model.parameters()])
        moved[way] = seen
        for b, after in zip(before, seen[0]):
            assert_bitwise(after, b, f"{way}: step 1 at lr 0")
        for a, b in zip(seen[1], seen[0]):
            assert not np.array_equal(a, b), f"{way}: step 2 moved nothing"
    assert_one_program(step)
    for k, (got, want) in enumerate(zip(moved["compiled"], moved["eager"])):
        for i, (g, w) in enumerate(zip(got, want)):
            assert_close(g, w, f"step {k + 1}, parameter {i}")


def test_two_steps_taking_turns_create_the_state_once():
    """Two compiled steps that take turns on one optimizer each hand it
    a whole state: ``create_state``'s pass is traced once, and each step
    holds one program."""
    model = make_model()
    o = opt.Adam(0.05, parameters=model.parameters())
    first = paddle.jit.train_step(model, o, loss_fn)
    second = paddle.jit.train_step(
        model, o, lambda m, x, y: 2.0 * loss_fn(m, x, y))
    for x, y in batches():
        first(x, y)
        second(x, y)
    assert_one_program(first)
    assert_one_program(second)
    assert o._create_state_jit._cache_size() == 1


def test_resume_from_a_state_dict_keeps_the_loaded_slots():
    """State loaded before the first step is kept as it is (the very
    arrays); only a missing slot is created; still one trace."""
    start = make_model()
    first = opt.Adam(0.05, parameters=start.parameters())
    run_eager(start, first, batches(n=1))
    saved = first.state_dict()
    dropped = next(iter(start.parameters())).name

    def load(start, model, o):
        names = {p.name: q.name for p, q in zip(start.parameters(),
                                                model.parameters())}
        state = {}
        for k, v in saved.items():
            if k.startswith("@"):
                state[k] = v
                continue
            pkey, slot = k.rsplit(".", 1)
            if (pkey, slot) != (dropped, "moment2"):
                state[names[pkey] + "." + slot] = jnp.array(v._data,
                                                            copy=True)
        o.set_state_dict(state)

    model = twin(start)
    o = opt.Adam(0.05, parameters=model.parameters())
    load(start, model, o)
    loaded = {(k, n): a for k, slots in o._accumulators.items()
              for n, a in slots.items()}
    o.create_state()
    assert sum(map(len, o._accumulators.values())) == len(loaded) + 1
    for (k, n), a in loaded.items():
        assert o._accumulators[k][n] is a, (k, n)

    runs = follow(lambda ps: opt.Adam(0.05, parameters=ps), start=start,
                  load=load)
    check(runs)
    assert list(runs["created"]) == [(0, "moment2")]


def test_a_parameter_without_gradient_gets_zero_slots():
    """A trainable parameter the loss never reaches: its slots are
    created (zeros) and carried unchanged, the parameter stays put."""
    paddle.seed(7)
    model = nn.Sequential(nn.Linear(8, 4))
    spare = nn.Linear(4, 4)
    o = opt.AdamW(0.05, parameters=list(model.parameters())
                  + list(spare.parameters()))
    before = [np.asarray(p._data) for p in spare.parameters()]
    step = paddle.jit.train_step(model, o, loss_fn)
    for x, y in batches():
        step(x, y)
    assert_one_program(step)
    for p, b in zip(spare.parameters(), before):
        assert_bitwise(p._data, b, p.name)
        for name in ("moment1", "moment2"):
            assert not np.any(np.asarray(o._accumulators[p.name][name]))


def test_committed_batches_keep_one_program():
    """Arguments committed to a device commit the step's outputs; the
    first call's carried state is committed to match."""
    model = make_model()
    o = opt.AdamW(0.05, parameters=model.parameters())
    step = paddle.jit.train_step(model, o, loss_fn)
    dev = jax.devices()[0]
    for x, y in batches():
        step(jax.device_put(x, dev), jax.device_put(y, dev))
    assert_one_program(step)


def test_state_reset_from_outside_is_created_again():
    """Optimizer state emptied between calls (a new seed, a fresh start)
    is created again before the next call: the step keeps its program."""
    model = make_model()
    o = opt.Adam(0.05, parameters=model.parameters())
    step = paddle.jit.train_step(model, o, loss_fn)
    feed = batches()
    step(*feed[0])
    o.load_opt_state({"acc": {}, "master": {}, "step": 0})
    step(*feed[1])
    step(*feed[2])
    assert_one_program(step)


def test_the_traced_call_is_marked_on_its_span():
    obs.reset()
    model = make_model()
    o = opt.Adam(0.05, parameters=model.parameters())
    step = paddle.jit.train_step(model, o, loss_fn)
    for x, y in batches():
        step(x, y)
    spans = [s for s in obs.tracer().spans() if s.name == "train.step"]
    assert [s.attributes.get("traced", False) for s in spans] == \
        [True, False, False]


def test_toy_bert_under_autocast_holds_one_program():
    """BERT-tiny, AdamW under ``auto_cast`` O1, through
    ``paddle.jit.train_step``: three steps and a fourth as a window would
    take it.  One program throughout; the compile ledger's ``step`` row
    counts one."""
    from paddle_tpu.models.bert import BertConfig, \
        BertForSequenceClassification
    obs.reset()
    paddle.seed(11)
    cfg = BertConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=32, num_labels=3)
    model = BertForSequenceClassification(cfg)
    model.train()
    o = opt.AdamW(1e-3, parameters=model.parameters(), weight_decay=0.01)

    def bert_loss(mod, ids, tts, y):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            logits = mod(ids, tts)
        return F.cross_entropy(logits, y)

    step = paddle.jit.train_step(model, o, bert_loss)
    rng = np.random.RandomState(5)
    tts = jnp.zeros((2, 16), jnp.int32)
    losses = []
    for _ in range(STEPS + 1):
        ids = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
        y = jnp.asarray(rng.randint(0, 3, (2,)), jnp.int32)
        losses.append(float(step(ids, tts, y)))
    assert np.all(np.isfinite(losses))
    assert_one_program(step)
    jits = obs.resource_tracker().snapshot()["compiles"]["jits"]
    assert jits["step"]["count"] == 1
    assert {"moment1", "moment2"} <= set(
        next(iter(o._accumulators.values())))
