"""Driver ``train``: ``FusedTrainer.step`` fed a host batch every step.

Set-up builds ONE trainer, gives it the seed's weights, and drives it
through its first three steps by the window's own call and feed (which
is also what compiles the step); the readings of those steps are what
``correct`` is decided on, and the same object then runs the window.
"""
import gc
import time

import numpy as np

from benchmark import check_train, harness, weights


class Driver:
    default_gap_label = "host_other"
    host_spans = ("feed", "step_call", "wait_prev")

    def __init__(self, cell, seed, rehearse=False):
        self.cell = cell
        self.seed = int(seed)
        self.rehearse = rehearse
        self.config = cell.config
        self.traffic = cell.traffic
        self.recipe = dict(cell.config["training"])
        self.family = harness.load_family(cell.config["family"])
        self.readings = None
        self.kernels_in_step = None

    # ------------------------------------------------------------ set-up
    def _trainer(self):
        import jax.numpy as jnp

        from mxnet_tpu.trainer import FusedTrainer

        r = self.recipe
        opt_params = {"lr": float(r["lr"])}
        scheduler = None
        if r["optimizer"] == "adam":
            # Kingma & Ba's bias correction rides in on the step size,
            # computed on the host each step (optim_rules.py says so)
            from benchmark.reference.optim import adam_lr

            scheduler = lambda t, lr=float(r["lr"]): adam_lr(lr, t)
        else:
            opt_params["momentum"] = float(r["momentum"])
            opt_params["rescale_grad"] = float(r["rescale_grad"])
        # FusedTrainer casts every input in ``data_names`` to the compute
        # dtype, and bfloat16 keeps 8 bits of a token id.  Inputs the
        # family marks as whole numbers go in as ``label_names``, which
        # are handed to the graph as they come (PERF.md, open questions).
        shapes = self.family.input_shapes(self.config, self.traffic)
        whole = set(getattr(self.family, "WHOLE_NUMBER_INPUTS", ()))
        labels = set(self.family.LABEL_INPUTS)
        tr = FusedTrainer(
            self.family.build_symbol(self.config, self.traffic),
            data_names=[k for k in shapes if k not in whole | labels],
            label_names=sorted(whole | labels),
            optimizer=r["optimizer"], optimizer_params=opt_params,
            dtype=jnp.dtype(r["compute_dtype"]), lr_scheduler=scheduler,
            initializer=lambda name, arr: None)
        tr.init(**shapes)
        return tr

    def _give_weights(self, tr):
        """The seed's weights into the trainer, in the layout it stores
        them; its optimizer state stays at the noughts ``init`` made."""
        import jax
        import jax.numpy as jnp

        pspecs, aspecs = self.family.train_specs(self.config, self.traffic)
        for what, specs, have in (("parameters", pspecs, tr.params),
                                  ("auxiliary states", aspecs, tr.aux)):
            mine = {k: tuple(v["shape"]) for k, v in specs.items()}
            logical = {k: tuple(tr._logical_param(k, v).shape)
                       if what == "parameters" else tuple(v.shape)
                       for k, v in have.items()}
            if mine != logical:
                odd = sorted(set(mine.items()) ^ set(logical.items()))[:6]
                raise RuntimeError(
                    f"the program's {what} are not the configuration's: "
                    f"{odd}")
        hwio = sorted(tr._hwio)
        self._hwio = hwio
        params = weights.make(pspecs, self.seed, jnp.float32)
        if hwio:
            flip = jax.jit(lambda p: {k: jnp.transpose(p[k], (2, 3, 1, 0))
                                      for k in hwio})
            params.update(flip({k: params[k] for k in hwio}))
        tr.params = params
        if aspecs:
            tr.aux = weights.make(aspecs, self.seed, jnp.float32)
        tr._refresh_compute_cache()

    def setup(self):
        import jax

        self.tr = self._trainer()
        self._give_weights(self.tr)
        pool = int(self.traffic["host_batch_pool"])
        self.batches = self.family.host_batches(
            self.config, self.traffic, self.seed, max(pool, 3))
        self._ce = _mean_ce()
        self._pending = []
        self.step_call_s = 0.0
        self.steps = 0
        losses, grad_norms = [], None
        for i in range(3):
            outs = self._one_step(i)
            losses.append(float(self._ce(
                outs[0], self.family.labels_of(self.batches[i]))))
            if i == 0:
                grad_norms = self._gradient_norms()
        change = self._change_norms()
        self.readings = {"losses": losses, "grad_norms": grad_norms,
                         "change_norms": change}
        del outs
        self._drain()
        jax.block_until_ready(self.tr.params)

    def count_kernels(self):
        """Custom calls in the compiled step's text (traced runs)."""
        text = self.tr.lower_step(**self.batches[0]).compile().as_text()
        self.kernels_in_step = text.count("tpu_custom_call")
        return self.kernels_in_step

    # ----------------------------------------------- the timed call and feed
    def _one_step(self, i):
        """One step as the window makes it: pick the next host batch,
        call ``step`` with it, and wait for the step before it, so that
        the host runs one step ahead of the device and no further."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("feed"):
            batch = self.batches[i % len(self.batches)]
        t0 = time.perf_counter()
        with TraceAnnotation("step_call"):
            outs = self.tr.step(**batch)
        self.step_call_s += time.perf_counter() - t0
        self._pending.append(outs[0])
        if len(self._pending) > 1:
            with TraceAnnotation("wait_prev"):
                self._pending.pop(0).block_until_ready()
        self.steps += 1
        return outs

    def _kernels_expected(self):
        """Pallas kernels the step has to hold, where they were counted
        (traced runs, on a TPU): the sum over the family's kernels."""
        if self.kernels_in_step is None or self.rehearse:
            return None
        work = self.family.kernel_work(self.config, self.traffic)
        return sum(k["calls_per_step"] for k in work.values())

    def _drain(self):
        for x in self._pending:
            x.block_until_ready()
        self._pending = []

    def window(self, seconds, tracer=None):
        import jax

        self.step_call_s, self.steps = 0.0, 0
        if tracer is not None:
            tracer.start()
        t0 = time.perf_counter()
        i = 3
        while time.perf_counter() - t0 < seconds:
            self._one_step(i)
            i += 1
        self._drain()
        jax.block_until_ready(self.tr.params)
        wall = time.perf_counter() - t0
        if tracer is not None:
            tracer.stop()
        c = self.config
        return {"window_s": wall, "steps": self.steps,
                "attempted": self.steps, "failed": 0,
                "step_call_s": self.step_call_s,
                "flops_per_step": self.family.step_flops(c, self.traffic),
                "kernel_work": self.family.kernel_work(c, self.traffic),
                "kernels_in_step": self.kernels_in_step,
                "kernels_expected": self._kernels_expected(),
                "end_to_end": {"train_step_ms": 1e3 * wall / self.steps}}

    # --------------------------------------------------- program's readings
    def _gradient_norms(self):
        """The first gradient as the optimizer got it, leaf by leaf,
        from the optimizer's state after one step: Adam's first moment
        is (1 - b1) g; momentum's buffer is -lr g."""
        import jax
        import jax.numpy as jnp

        r = self.recipe
        scale = (1.0 / (1.0 - 0.9) if r["optimizer"] == "adam"
                 else -1.0 / float(r["lr"]))

        @jax.jit
        def norms(state):
            return {k: jnp.sqrt(jnp.sum(jnp.square(s[0]))) * abs(scale)
                    for k, s in state.items()}

        return {k: float(v) for k, v in
                jax.device_get(norms(self.tr.opt_state)).items()}

    def _change_norms(self):
        """|params now - the seed's weights|, leaf by leaf; the start is
        drawn again from the seed, never kept."""
        import jax
        import jax.numpy as jnp

        pspecs, _ = self.family.train_specs(self.config, self.traffic)
        hwio = set(self._hwio)
        key = weights.seed_key(self.seed)

        @jax.jit
        def norms(params, key):
            out = {}
            for k, v in params.items():
                p0 = weights._leaf(key, k, pspecs[k], jnp.float32, None)
                if k in hwio:
                    p0 = jnp.transpose(p0, (2, 3, 1, 0))
                out[k] = jnp.sqrt(jnp.sum(jnp.square(v - p0)))
            return out

        return {k: float(v) for k, v in
                jax.device_get(norms(self.tr.params, key)).items()}

    # -------------------------------------------------------------- after
    def free(self):
        tr = self.tr
        tr.params = tr._cparams = tr.opt_state = tr.aux = None
        tr._step_fn = None
        self.tr = None
        self._pending = []
        gc.collect()

    def check(self):
        limits = self.cell.limits
        ref = check_train.reference_readings(
            self.family, self.config, self.recipe, self.seed,
            self.batches[:3])
        rows, notes = check_train.compare(self.readings, ref, limits)
        print("check: program losses %s reference losses %s; worst leaves "
              "%s" % (self.readings["losses"], ref["losses"], notes),
              flush=True)
        return rows


def _mean_ce():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ce(probs, labels):
        p = probs.astype(jnp.float32).reshape(-1, probs.shape[-1])
        idx = labels.astype(jnp.int32).reshape(-1, 1)
        picked = jnp.take_along_axis(p, idx, axis=1)[:, 0]
        return -jnp.mean(jnp.log(jnp.maximum(picked, 1e-30)))

    return ce


def calibrate(cell, seed, _seconds, others, rehearse=False):
    """Readings of one seed: the program against the reference, and on
    ``others`` seeds the control (the reference in float8) and the
    planted faults against the same reference."""
    no_limit = {"loss_gap": float("inf"), "grad_norm_gap": float("inf"),
                "change_norm_gap": float("inf")}
    d = Driver(cell, seed, rehearse=rehearse)
    d.setup()
    d.free()
    args = (d.family, d.config, d.recipe, d.seed, d.batches[:3])
    ref = check_train.reference_readings(*args)
    rows, notes = check_train.compare(d.readings, ref, no_limit)
    yield {"kind": "program", "notes": notes,
           **{n: v for n, v, _l in rows}}
    if not others:
        return
    for kind, kw in (("control_fp8", {"compute": "fp8"}),
                     ("fault_half_batch", {"fault": "half_batch"}),
                     ("fault_state_unchanged",
                      {"fault": "state_unchanged"})):
        got = check_train.reference_readings(*args, **kw)
        rows, notes = check_train.compare(got, ref, no_limit)
        yield {"kind": kind, "notes": notes, **{n: v for n, v, _l in rows}}
