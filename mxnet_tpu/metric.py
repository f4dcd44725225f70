"""Evaluation metrics (parity: python/mxnet/metric.py).

EvalMetric registry: Accuracy, TopKAccuracy, F1, MAE/MSE/RMSE,
CrossEntropy, Perplexity, Loss, CustomMetric (+np wrapper),
CompositeEvalMetric.

Two accumulation paths:

- **fused (default)** — each built-in metric contributes a jitted
  ``(sum, num) += f(label, pred)`` accumulator whose running totals live
  as DEVICE scalars: ``update()`` only *enqueues* one async dispatch, and
  the device→host sync happens when a reader (``get()`` /
  ``get_name_value()`` / ``reset_local()``) actually needs the values.
  This is what keeps the training hot loop free of per-batch ``asnumpy``
  stalls (the reference syncs every batch: SURVEY.md §3.1 update_metric).
  ``MXTPU_FUSED_METRICS=0`` opts out.
- **eager** — the reference's host-numpy path, used automatically for
  ``CustomMetric``/``mx.metric.np`` callbacks, F1/Torch, multi-output
  (``num=``) metrics, and non-array inputs.

Both paths share the accumulators, so fused and eager updates can
interleave freely (a fused window is folded in before any eager read).
"""
from __future__ import annotations

import os

import numpy as _np

from . import telemetry as _tm
from .base import MXNetError, parse_bool

# --- telemetry families (docs/telemetry.md) --------------------------------
_TM_FUSED = _tm.counter(
    "metric_fused_update_total",
    "metric updates accumulated device-side (no host sync)",
    labels=("metric",))
_TM_SYNC = _tm.counter(
    "metric_host_sync_total",
    "device->host metric syncs: fused-path drains (one per value read "
    "with pending updates) + eager-path asnumpy updates (one per "
    "label/pred pair)", labels=("metric",))


def fused_metrics_enabled() -> bool:
    """MXTPU_FUSED_METRICS gate (default on)."""
    return parse_bool(os.environ.get("MXTPU_FUSED_METRICS", "1"))


def _device_raw(x):
    """The raw jax array behind a metric input, WITHOUT a host sync —
    or None when the input has no device representation (plain numpy /
    lists take the eager path)."""
    import jax

    read = getattr(x, "_read", None)  # NDArray (views resolve lazily)
    if read is not None:
        return read()
    if isinstance(x, jax.Array):
        return x
    return None


def check_label_shapes(labels, preds, shape=False):
    if len(labels) != len(preds):
        raise MXNetError(f"label/pred count mismatch: {len(labels)} vs {len(preds)}")


class EvalMetric:
    """Base metric with a local/global accumulator split.

    Subclasses only ever touch ``sum_metric``/``num_inst`` (the *local*
    window).  ``reset_local()`` folds the window into carried totals and
    clears it — so interval reporters (Speedometer auto_reset) can print
    per-window values while ``get_global_name_value()`` still returns the
    true since-``reset()`` aggregate for epoch-end logs.  (The v0.9.4
    reference lacks this split and its epoch log after an auto_reset
    Speedometer covers only the tail window; later MXNet added
    reset_local/get_global, which is the behavior reproduced here.)

    Fused accumulation: a subclass that defines ``_fused_delta(label,
    pred) -> (sum_delta, num_delta)`` (pure jnp, traceable) gets the
    device-resident path for free — its ``update`` calls
    ``_fused_accumulate`` per (label, pred) pair and only falls through
    to its eager numpy body when the fused path is unavailable.
    """

    # subclasses override with a jnp-traceable method; None = eager-only
    _fused_delta = None

    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self._fused_jit = None
        # bumped on every fused enqueue: together with the (host-cheap)
        # accumulator values it forms update_stamp(), the sync-free
        # "anything new since I last looked?" token Speedometer uses
        self._version = 0
        self.reset()

    def reset(self):
        # pending device window is DISCARDED, not synced — reset means
        # "forget everything", same as zeroing the host accumulators
        self._dev_sum = None
        self._dev_num = None
        if self.num is None:
            self.num_inst = 0
            self.sum_metric = 0.0
            self._carried_num = 0
            self._carried_sum = 0.0
        else:
            self.num_inst = [0] * self.num
            self.sum_metric = [0.0] * self.num
            self._carried_num = [0] * self.num
            self._carried_sum = [0.0] * self.num

    def reset_local(self):
        """Fold the current window into the global totals and clear it."""
        self._drain()
        if self.num is None:
            self._carried_num += self.num_inst
            self._carried_sum += self.sum_metric
            self.num_inst = 0
            self.sum_metric = 0.0
        else:
            for i in range(self.num):
                self._carried_num[i] += self.num_inst[i]
                self._carried_sum[i] += self.sum_metric[i]
            self.num_inst = [0] * self.num
            self.sum_metric = [0.0] * self.num

    # ------------------------------------------------------------- fused path
    def _fused_fn(self):
        if self._fused_delta is None:
            return None
        if self._fused_jit is None:
            import jax

            delta = self._fused_delta

            def acc(s, n, label, pred):
                ds, dn = delta(label, pred)
                return s + ds, n + dn

            self._fused_jit = jax.jit(acc)
        return self._fused_jit

    def _fused_accumulate(self, label, pred) -> bool:
        """Try to fold one (label, pred) pair into the device window.

        Returns False (caller runs its eager numpy body) when fused
        metrics are disabled, the metric has no fused kernel or uses
        multi-output accumulators, or the inputs are not device arrays.
        On success the accumulate is ONE async dispatch — no host sync.
        """
        if self.num is not None or not fused_metrics_enabled():
            return False
        fn = self._fused_fn()
        if fn is None:
            return False
        raw_p = _device_raw(pred)
        if raw_p is None:
            return False
        if label is None:
            raw_l = 0.0  # label-free metrics (Loss) ignore it
        else:
            raw_l = _device_raw(label)
            if raw_l is None:
                return False
        import jax
        import jax.numpy as jnp

        # sharded preds (data-parallel executor group): every jit input
        # must live on the same device set, so the accumulators (and a
        # host-resident label) are replicated over the pred's mesh
        rep = None
        sh = getattr(raw_p, "sharding", None)
        if sh is not None and len(sh.device_set) > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            if not isinstance(sh, NamedSharding):
                return False  # unknown multi-device layout: eager path

            def _replicate(val, _rep):
                # a mesh spanning other processes cannot device_put a
                # committed local array (non-addressable devices): each
                # process contributes its addressable shards of the
                # replicated value instead (docs/multihost.md)
                import numpy as _np

                me = jax.process_index()
                if all(d.process_index == me for d in _rep.device_set):
                    return jax.device_put(val, _rep)
                host = _np.asarray(val)
                return jax.make_array_from_callback(
                    host.shape, _rep, lambda idx, _h=host: _h[idx])

            rep = NamedSharding(sh.mesh, PartitionSpec())
            if label is None:
                raw_l = _replicate(jnp.float32(0.0), rep)
            elif len(getattr(raw_l, "sharding",
                             sh).device_set) != len(sh.device_set):
                raw_l = _replicate(raw_l, rep)
        if (rep is None and self._dev_sum is not None
                and len(self._dev_sum.sharding.device_set) > 1):
            # mesh -> single-device transition (metric reused across
            # modules): fold the sharded window out rather than mixing
            self._drain()
        # one device: the label and the accumulators follow the
        # prediction's device.  A label batch out of a host iterator is
        # committed to the CPU backend while a Module bound to mx.tpu(0)
        # predicts on the chip, and jit refuses to mix the two (found by
        # the first Module.fit on a chip, PR 21)
        home = None
        if rep is None and sh is not None:
            (dev,) = sh.device_set
            home = jax.sharding.SingleDeviceSharding(dev)
            if label is not None and raw_l.devices() != {dev}:
                raw_l = jax.device_put(raw_l, home)
        if self._dev_sum is None:
            self._dev_sum = jnp.zeros((), jnp.float32, device=home)
            self._dev_num = jnp.zeros((), jnp.float32, device=home)
        elif home is not None and self._dev_sum.devices() != {dev}:
            self._dev_sum = jax.device_put(self._dev_sum, home)
            self._dev_num = jax.device_put(self._dev_num, home)
        if rep is not None and len(
                self._dev_sum.sharding.device_set) != len(sh.device_set):
            self._dev_sum = _replicate(self._dev_sum, rep)
            self._dev_num = _replicate(self._dev_num, rep)
        self._dev_sum, self._dev_num = fn(self._dev_sum, self._dev_num,
                                          raw_l, raw_p)
        self._version += 1
        if _tm.enabled():
            _TM_FUSED.inc(metric=self.name)
        return True

    def _drain(self):
        """Fold the device window into the host accumulators.  This is
        the ONLY device→host sync point of the fused path."""
        if self._dev_sum is None:
            return
        s, n = self._dev_sum, self._dev_num
        self._dev_sum = None
        self._dev_num = None
        self.sum_metric += float(s)
        n = float(n)
        # eager counts are ints (len(label)); keep that type when exact
        self.num_inst += int(n) if n.is_integer() else n
        if _tm.enabled():
            _TM_SYNC.inc(metric=self.name)

    def _eager_sync(self):
        """Record one eager-path device->host sync (an update pair that
        went through asnumpy) in the same family the fused drains use —
        the fused-vs-eager sync count is the bench's pipeline story."""
        if _tm.enabled():
            _TM_SYNC.inc(metric=self.name)

    def update_stamp(self):
        """Cheap sync-free token that changes whenever this metric has
        received updates (Speedometer's "values needed" guard): fused
        enqueues bump ``_version``; eager updates move the host
        accumulators directly."""

        def _t(v):
            return tuple(v) if isinstance(v, list) else v

        return (self._version, _t(self.num_inst), _t(self.sum_metric))

    def update(self, labels, preds):
        raise NotImplementedError

    def _value(self, s, n):
        """Accumulators -> reported value; metrics with a non-mean readout
        (e.g. Perplexity's exp) override THIS so local and global views
        stay consistent."""
        return s / n if n else float("nan")

    def get(self):
        self._drain()
        if self.num is None:
            return (self.name, self._value(self.sum_metric, self.num_inst))
        names = [f"{self.name}_{i}" for i in range(self.num)]
        values = [self._value(s, n)
                  for s, n in zip(self.sum_metric, self.num_inst)]
        return (names, values)

    def get_global(self):
        self._drain()
        if self.num is None:
            return (self.name, self._value(self._carried_sum + self.sum_metric,
                                           self._carried_num + self.num_inst))
        names = [f"{self.name}_{i}" for i in range(self.num)]
        values = [
            self._value(cs + s, cn + n)
            for cs, s, cn, n in zip(self._carried_sum, self.sum_metric,
                                    self._carried_num, self.num_inst)
        ]
        return (names, values)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            return [(name, value)]
        return list(zip(name, value))

    def get_global_name_value(self):
        name, value = self.get_global()
        if not isinstance(name, list):
            return [(name, value)]
        return list(zip(name, value))


class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics=None, name="composite"):
        super().__init__(name)
        self.metrics = metrics or []

    def add(self, metric):
        self.metrics.append(metric)

    def reset(self):
        self._dev_sum = None
        self._dev_num = None
        for m in getattr(self, "metrics", []):
            m.reset()

    def reset_local(self):
        for m in self.metrics:
            m.reset_local()

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)

    def update_stamp(self):
        return tuple(m.update_stamp() for m in self.metrics)

    def get(self):
        names, values = [], []
        for m in self.metrics:
            n, v = m.get()
            names.append(n)
            values.append(v)
        return (names, values)

    def get_global(self):
        names, values = [], []
        for m in self.metrics:
            n, v = m.get_global()
            names.append(n)
            values.append(v)
        return (names, values)


class Accuracy(EvalMetric):
    """Parity: metric.py Accuracy — argmax over axis 1 when needed.

    ``ignore_label`` drops masked entries (padding frames in bucketed
    sequence training) from both numerator and denominator, pairing with
    SoftmaxOutput(use_ignore=True)."""

    def __init__(self, ignore_label=None):
        super().__init__("accuracy")
        self.ignore_label = ignore_label

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        label = label.astype(jnp.int32)
        if pred.ndim > 1 and pred.shape != label.shape:
            pred = pred.argmax(axis=1)
        pred = pred.astype(jnp.int32).reshape(-1)
        label = label.reshape(-1)
        if self.ignore_label is not None:
            keep = label != self.ignore_label
            return (((pred == label) & keep).sum().astype(jnp.float32),
                    keep.sum().astype(jnp.float32))
        return ((pred == label).sum().astype(jnp.float32),
                jnp.float32(label.size))

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            if self._fused_accumulate(label, pred):
                continue
            self._eager_sync()
            pred_np = pred.asnumpy()
            label_np = label.asnumpy().astype(_np.int32)
            if pred_np.ndim > 1 and pred_np.shape != label_np.shape:
                pred_np = pred_np.argmax(axis=1)
            pred_np = pred_np.astype(_np.int32).reshape(-1)
            label_np = label_np.reshape(-1)
            if self.ignore_label is not None:
                keep = label_np != self.ignore_label
                pred_np, label_np = pred_np[keep], label_np[keep]
            self.sum_metric += float((pred_np == label_np).sum())
            self.num_inst += len(label_np)


class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1):
        super().__init__(f"top_k_accuracy_{top_k}")
        self.top_k = top_k

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        label = label.astype(jnp.int32).reshape(-1)
        argsorted = jnp.argsort(-pred, axis=1)[:, : self.top_k]
        hits = (argsorted == label[:, None]).any(axis=1).sum()
        return hits.astype(jnp.float32), jnp.float32(label.size)

    def update(self, labels, preds):
        for label, pred in zip(labels, preds):
            if self._fused_accumulate(label, pred):
                continue
            self._eager_sync()
            pred_np = pred.asnumpy()
            label_np = label.asnumpy().astype(_np.int32).reshape(-1)
            argsorted = _np.argsort(-pred_np, axis=1)[:, : self.top_k]
            self.sum_metric += float((argsorted == label_np[:, None]).any(axis=1).sum())
            self.num_inst += len(label_np)


class F1(EvalMetric):
    """Binary F1 (parity: metric.py F1).  Eager-only: the per-batch F1
    readout is not a (sum, num) fold."""

    def __init__(self):
        super().__init__("f1")

    def update(self, labels, preds):
        for label, pred in zip(labels, preds):
            pred_np = pred.asnumpy()
            label_np = label.asnumpy().astype(_np.int32).reshape(-1)
            if pred_np.ndim > 1:
                pred_np = pred_np.argmax(axis=1)
            pred_np = pred_np.astype(_np.int32).reshape(-1)
            tp = float(((pred_np == 1) & (label_np == 1)).sum())
            fp = float(((pred_np == 1) & (label_np == 0)).sum())
            fn = float(((pred_np == 0) & (label_np == 1)).sum())
            precision = tp / (tp + fp) if tp + fp > 0 else 0.0
            recall = tp / (tp + fn) if tp + fn > 0 else 0.0
            f1 = 2 * precision * recall / (precision + recall) if precision + recall > 0 else 0.0
            self.sum_metric += f1
            self.num_inst += 1


class MAE(EvalMetric):
    def __init__(self):
        super().__init__("mae")

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        err = jnp.abs(label.reshape(pred.shape) - pred).mean()
        return err.astype(jnp.float32), jnp.float32(1.0)

    def update(self, labels, preds):
        for label, pred in zip(labels, preds):
            if self._fused_accumulate(label, pred):
                continue
            self._eager_sync()
            l, p = label.asnumpy(), pred.asnumpy()
            self.sum_metric += float(_np.abs(l.reshape(p.shape) - p).mean())
            self.num_inst += 1


class MSE(EvalMetric):
    def __init__(self):
        super().__init__("mse")

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        err = ((label.reshape(pred.shape) - pred) ** 2).mean()
        return err.astype(jnp.float32), jnp.float32(1.0)

    def update(self, labels, preds):
        for label, pred in zip(labels, preds):
            if self._fused_accumulate(label, pred):
                continue
            self._eager_sync()
            l, p = label.asnumpy(), pred.asnumpy()
            self.sum_metric += float(((l.reshape(p.shape) - p) ** 2).mean())
            self.num_inst += 1


class RMSE(EvalMetric):
    def __init__(self):
        super().__init__("rmse")

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        err = jnp.sqrt(((label.reshape(pred.shape) - pred) ** 2).mean())
        return err.astype(jnp.float32), jnp.float32(1.0)

    def update(self, labels, preds):
        for label, pred in zip(labels, preds):
            if self._fused_accumulate(label, pred):
                continue
            self._eager_sync()
            l, p = label.asnumpy(), pred.asnumpy()
            self.sum_metric += float(_np.sqrt(((l.reshape(p.shape) - p) ** 2).mean()))
            self.num_inst += 1


class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-8):
        super().__init__("cross-entropy")
        self.eps = eps

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        label = label.astype(jnp.int32).reshape(-1)
        prob = pred[jnp.arange(label.shape[0]), label]
        return ((-jnp.log(prob + self.eps)).sum().astype(jnp.float32),
                jnp.float32(label.shape[0]))

    def update(self, labels, preds):
        for label, pred in zip(labels, preds):
            if self._fused_accumulate(label, pred):
                continue
            self._eager_sync()
            label_np = label.asnumpy().astype(_np.int32).reshape(-1)
            pred_np = pred.asnumpy()
            prob = pred_np[_np.arange(label_np.shape[0]), label_np]
            self.sum_metric += float((-_np.log(prob + self.eps)).sum())
            self.num_inst += label_np.shape[0]


class Perplexity(EvalMetric):
    """exp(mean NLL) for language models; ``ignore_label`` entries
    (padding from bucketing) are excluded (parity: mx.metric.Perplexity
    as used by example/rnn training scripts)."""

    def __init__(self, ignore_label=None, axis=-1):
        super().__init__("Perplexity")
        self.ignore_label = ignore_label
        self.axis = axis

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        label = label.astype(jnp.int32).reshape(-1)
        if self.axis not in (-1, pred.ndim - 1):
            pred = jnp.moveaxis(pred, self.axis, -1)
        pred = pred.reshape(label.shape[0], -1)
        prob = pred[jnp.arange(label.shape[0]),
                    jnp.clip(label, 0, pred.shape[1] - 1)]
        nll = -jnp.log(jnp.maximum(prob, 1e-10))
        if self.ignore_label is not None:
            mask = label != self.ignore_label
            return ((nll * mask).sum().astype(jnp.float32),
                    mask.sum().astype(jnp.float32))
        return nll.sum().astype(jnp.float32), jnp.float32(label.shape[0])

    def update(self, labels, preds):
        fused_all = True
        loss, num = 0.0, 0
        for label, pred in zip(labels, preds):
            if self._fused_accumulate(label, pred):
                continue
            self._eager_sync()
            fused_all = False
            label_np = label.asnumpy().astype(_np.int32).reshape(-1)
            pred_np = pred.asnumpy()
            if self.axis not in (-1, pred_np.ndim - 1):
                pred_np = _np.moveaxis(pred_np, self.axis, -1)
            pred_np = pred_np.reshape(label_np.shape[0], -1)
            prob = pred_np[_np.arange(label_np.shape[0]),
                           _np.clip(label_np, 0, pred_np.shape[1] - 1)]
            mask = _np.ones_like(prob, dtype=bool)
            if self.ignore_label is not None:
                mask = label_np != self.ignore_label
            loss += float(-_np.log(_np.maximum(prob[mask], 1e-10)).sum())
            num += int(mask.sum())
        if not fused_all:
            self.sum_metric += loss
            self.num_inst += num

    def _value(self, s, n):
        return float(_np.exp(s / n)) if n else float("nan")


class Loss(EvalMetric):
    """Mean of the raw loss outputs (parity: mx.metric.Loss — "dummy"
    metric for printing a MakeLoss/LinearRegressionOutput head).  Labels
    are ignored."""

    def __init__(self, name="loss"):
        super().__init__(name)

    def _fused_delta(self, label, pred):
        import jax.numpy as jnp

        return (pred.sum().astype(jnp.float32), jnp.float32(pred.size))

    def update(self, labels, preds):
        for pred in preds:
            if self._fused_accumulate(None, pred):
                continue
            self._eager_sync()
            pred_np = pred.asnumpy()
            self.sum_metric += float(pred_np.sum())
            self.num_inst += pred_np.size


class Torch(EvalMetric):
    """Parity stub: metric.py Torch (average of preds)."""

    def __init__(self, name="torch"):
        super().__init__(name)

    def update(self, labels, preds):
        for pred in preds:
            self.sum_metric += float(pred.asnumpy().mean())
        self.num_inst += 1


class CustomMetric(EvalMetric):
    def __init__(self, feval, name=None, allow_extra_outputs=False):
        super().__init__(name or getattr(feval, "__name__", "custom"))
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            res = self._feval(label.asnumpy(), pred.asnumpy())
            if isinstance(res, tuple):
                s, n = res
                self.sum_metric += s
                self.num_inst += n
            else:
                self.sum_metric += res
                self.num_inst += 1


def np_metric(name=None, allow_extra_outputs=False):
    """Parity: mx.metric.np decorator."""

    def deco(feval):
        return CustomMetric(feval, name, allow_extra_outputs)

    return deco


def np(numpy_feval=None, name=None, allow_extra_outputs=False):
    """Parity: mx.metric.np — wrap a numpy function as an EvalMetric.

    Usable both ways the reference allows:
      mx.metric.np(CRPS)                      # direct wrap
      @mx.metric.np                            # bare decorator
      @mx.metric.np(name="crps")               # configured decorator
    """
    if callable(numpy_feval):
        return CustomMetric(numpy_feval, name, allow_extra_outputs)
    return np_metric(name=name, allow_extra_outputs=allow_extra_outputs)

_METRICS = {
    "acc": Accuracy,
    "accuracy": Accuracy,
    "f1": F1,
    "mae": MAE,
    "mse": MSE,
    "rmse": RMSE,
    "ce": CrossEntropy,
    "cross-entropy": CrossEntropy,
    "torch": Torch,
    "loss": Loss,
    "perplexity": Perplexity,
}


def create(metric, **kwargs):
    """Parity: mx.metric.create."""
    if callable(metric):
        return CustomMetric(metric, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        comp = CompositeEvalMetric()
        for m in metric:
            comp.add(create(m, **kwargs))
        return comp
    if isinstance(metric, str):
        if metric.startswith("top_k_accuracy"):
            parts = metric.split("_")
            return TopKAccuracy(top_k=int(parts[-1])) if parts[-1].isdigit() else TopKAccuracy()
        if metric.lower() in _METRICS:
            return _METRICS[metric.lower()](**kwargs)
    raise MXNetError(f"unknown metric {metric}")
