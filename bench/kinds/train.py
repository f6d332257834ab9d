"""Driver of the training cells: the paper's data-parallel program.

Set-up builds ONE compiled step (``train/step.jit_train_step``, the
faithful flat engine on a data mesh of the cell's chips) with its state,
made from the seed, and drives it through its first ``check_steps`` steps
on the window's own feed: fresh rows for every step, put on the device
from the host.  The window then goes on with the same object.  Once the
window has closed, the state is freed and the plain reference follows
the same first steps from the same weights; ``correct`` compares each
step's loss, the first gradient as the optimizer got it (read from Adam's
first moment after one step), and the parameters' change over the check
steps, leaf by leaf.

Traffic keys: ``seq``, ``rows_per_chip``, ``check_steps``,
``ref_block_rows`` (reference rows per block), ``trace_steps`` (steps a
traced run traces), ``optimizer`` (OptConfig fields), ``limits`` (one per
number compared).
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import compare, flops, generators, harness, trace_reduce

NUMBERS = ("loss", "grad", "update")


def weights_key(seed: int):
    import jax

    return jax.random.PRNGKey(int(generators.rng(seed, 0).integers(0, 2**31)))


class Program:
    """The compiled step of one cell, its shardings, and the jitted helpers
    that read its state; built once per process, driven from any seed."""

    def __init__(self, cell: harness.Cell, devices):
        import jax
        from jax.sharding import AxisType, Mesh

        from repro.configs.base import ShapeConfig
        from repro.models.common import ShardRules
        from repro.optim import OptConfig
        from repro.train.step import (
            TrainSettings, jit_train_step, opt_state_template)

        tr = cell.traffic
        self.cell, self.adapter, self.ref = cell, cell.adapter(), cell.ref()
        self.cfg = self.adapter.arch(cell.config)
        self.chips = len(devices)
        self.rows = int(tr["rows_per_chip"]) * self.chips
        self.seq = int(tr["seq"])
        self.mesh = Mesh(np.array(devices).reshape(self.chips, 1),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        rules = ShardRules.for_mesh(self.mesh, faithful=True)
        self.opt = OptConfig(kind="adam", **tr["optimizer"])
        settings = TrainSettings(faithful=True)
        shape = ShapeConfig(cell.name, "train", self.seq, self.rows)
        stepf, sds, in_sh = jit_train_step(
            self.cfg, self.mesh, rules, self.opt, shape, settings)
        if stepf._flat_engine != "faithful":
            raise harness.SetupError(
                f"train step lowered to {stepf._flat_engine!r}, not the "
                f"faithful flat engine")
        self.flat_len = stepf._flat_layout.total
        self.n_params = flops.param_count(sds[0])
        self.step = stepf.lower(*sds).compile()
        self.batch_sharding = in_sh[2]["tokens"]
        opt_init, _ = opt_state_template(self.cfg, self.mesh, rules, self.opt,
                                         settings)
        ref, adapter, cfgd = self.ref, self.adapter, cell.config
        self.make_params = jax.jit(
            lambda key: adapter.to_program(ref.init(cfgd, key)),
            out_shardings=in_sh[0])
        self.make_opt = jax.jit(opt_init, out_shardings=in_sh[1])
        self.norms = jax.jit(
            lambda t: compare.leaf_norms(adapter.from_program(t)))
        self.diff_norms = jax.jit(lambda a, b: compare.leaf_norms(
            adapter.from_program(jax.tree.map(lambda x, y: x - y, a, b))))

    def feed(self, seed: int, step: int):
        import jax

        rows = generators.train_tokens(seed, step, self.rows, self.seq,
                                       self.cfg.vocab)
        return {"tokens": jax.device_put(rows, self.batch_sharding)}

    def checked_start(self, seed: int, steps: int, annotate):
        """Weights from the seed, then the first ``steps`` steps through the
        compiled step.  Returns the state and the readings compared."""
        key = weights_key(seed)
        params = self.make_params(key)
        state = self.make_opt(params)
        losses, grad = [], None
        for i in range(steps):
            with annotate("bench.feed"):
                batch = self.feed(seed, i)
            with annotate("bench.dispatch"):
                params, state, metrics = self.step(params, state, batch)
            losses.append(float(metrics["loss"]))
            if i == 0:
                grad = np.asarray(self.norms(state["m"])) / (1.0 - self.opt.beta1)
        p0 = self.make_params(key)
        update = np.asarray(self.diff_norms(params, p0))
        del p0
        return params, state, {"losses": losses, "grad": grad, "update": update}


def reference_readings(cell: harness.Cell, seed: int, *, policy: str = "f32",
                       row_share: float = 1.0, devices=None) -> dict:
    """The plain reference through the first ``check_steps`` steps from the
    seed's weights: clip by global norm, then Adam (Kingma and Ba, with
    bias correction).  ``policy`` and ``row_share`` make the control and
    the half-batch fault.  The blocks of rows of a step are spread over
    ``devices`` and their gradients summed on the first."""
    import jax
    import jax.numpy as jnp

    devices = list(devices or jax.devices()[:1])
    tr, ref, cfgd = cell.traffic, cell.ref(), cell.config
    opt = tr["optimizer"]
    rows = int(tr["rows_per_chip"]) * cell.chips
    use = max(1, int(rows * row_share))
    block = int(tr["ref_block_rows"])
    seq, vocab = int(tr["seq"]), int(cfgd["vocab_size"])
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(
            lambda w, t: ref.loss(cfgd, w, t, policy)))
        acc = jax.jit(lambda a, g, f: jax.tree.map(lambda x, y: x + f * y, a, g),
                      donate_argnums=0)

        @jax.jit
        def adam(w, g, m, v, t):
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
            g = jax.tree.map(
                lambda x: x * jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-12)), g)
            b1, b2 = opt["beta1"], opt["beta2"]
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            c1, c2 = 1 - b1 ** t, 1 - b2 ** t
            w = jax.tree.map(lambda w, m, v: w - opt["lr"] * (m / c1) / (
                jnp.sqrt(v / c2) + opt["eps"]), w, m, v)
            return w, m, v, compare.leaf_norms(g)

        key = weights_key(seed)
        w0 = jax.device_put(jax.jit(lambda k: ref.init(cfgd, k))(key), devices[0])
        w = jax.tree.map(jnp.copy, w0)
        m = jax.tree.map(jnp.zeros_like, w0)
        v = jax.tree.map(jnp.zeros_like, w0)
        losses, grad = [], None
        for t in range(1, int(tr["check_steps"]) + 1):
            toks = generators.train_tokens(seed, t - 1, rows, seq, vocab)[:use]
            ws = [jax.device_put(w, d) for d in devices]
            gs, parts_loss = [None] * len(devices), []
            for j, b in enumerate(range(0, use, block)):
                k = j % len(devices)
                part = toks[b:b + block]
                loss, gb = grad_fn(ws[k], jax.device_put(part, devices[k]))
                f = part.shape[0] / use
                parts_loss.append(loss * f)
                gs[k] = jax.tree.map(lambda x: f * x, gb) if gs[k] is None \
                    else acc(gs[k], gb, f)
            g = None
            for gk in gs:
                if gk is not None:
                    gk = jax.device_put(gk, devices[0])
                    g = gk if g is None else acc(g, gk, 1.0)
            del ws, gs
            w, m, v, gn = adam(w, g, m, v, jnp.float32(t))
            losses.append(sum(float(x) for x in parts_loss))
            if t == 1:
                grad = np.asarray(gn)
            del g
        update = np.asarray(jax.jit(lambda a, b: compare.leaf_norms(
            jax.tree.map(lambda x, y: x - y, a, b)))(w, w0))
    return {"losses": losses, "grad": grad, "update": update}


def numbers(got: dict, ref: dict) -> dict[str, float]:
    """The numbers compared, program (or control) against reference."""
    keep = compare.moving_leaves(ref["grad"])
    return {
        "loss": compare.loss_gap(got["losses"], ref["losses"]),
        "grad": compare.worst_leaf_gap(got["grad"], ref["grad"])[0],
        "update": compare.worst_leaf_gap(got["update"], ref["update"], keep)[0],
    }


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, devices, scratch) -> dict:
    import jax

    prog = Program(cell, devices)
    annotate = jax.profiler.TraceAnnotation
    steps0 = int(cell.traffic["check_steps"])
    params, state, got = prog.checked_start(seed, steps0, annotate)
    jax.block_until_ready((params, state))

    tokens_per_step = prog.rows * prog.seq
    trace_dir = scratch / "trace" if trace else None
    # a traced run traces its first trace_steps steps: a trace holds some
    # hundred thousand operations a step
    last = int(cell.traffic["trace_steps"]) if trace else -1
    with (trace_reduce.profile(trace_dir) if trace else contextlib.nullcontext()):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        step, prev = steps0, None
        while True:
            with annotate("bench.feed"):
                batch = prog.feed(seed, step)
            with annotate("bench.dispatch"):
                params, state, metrics = prog.step(params, state, batch)
            if prev is not None:
                with annotate("bench.wait"):
                    prev["loss"].block_until_ready()
            prev, step = metrics, step + 1
            if time.perf_counter() - t0 >= seconds or step - steps0 == last:
                break
        with annotate("bench.wait"):
            jax.block_until_ready((params, state, metrics))
        window_s = time.perf_counter() - t0
    done = step - steps0
    final_loss = float(metrics["loss"])
    device = harness.device_record(devices)
    del params, state, metrics, prev, batch, prog.step

    out = {
        "setup_s": setup_s,
        "e2e": {"train_tokens_per_s": done * tokens_per_step / window_s},
        "attempted": done + steps0,
        "failed": 0 if np.isfinite(final_loss) else 1,
        "device": device,
        "record": {"kind": "train", "chips": prog.chips, "steps": done,
                   "window_s": window_s, "tokens": done * tokens_per_step,
                   "flops_per_token": flops.train_flops_per_token(
                       prog.n_params, attn_layers=prog.cfg.n_layers,
                       heads=prog.cfg.n_heads, head_dim=prog.cfg.head_dim,
                       seq=prog.seq),
                   "flat_len": prog.flat_len, "device_kind": device["kind"]},
    }
    if trace:
        summary = trace_reduce.reduce_dir(trace_dir)
        out["record"]["trace"] = summary
        out["device"] = {**device, "busy_s": summary["busy_s"],
                         "window_s": summary["window_s"]}
        out["breakdown"] = summary["breakdown"]

    want = reference_readings(cell, seed, devices=devices)
    lim = cell.traffic["limits"]
    vals = numbers(got, want)
    out["checks"] = [harness.Check(k, vals[k], float(lim[k])) for k in NUMBERS]
    return out
