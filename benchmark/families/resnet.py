"""Family adapter: residual networks for image classification
(``"family": "resnet"``)."""
import numpy as np

from benchmark import weights, work
from benchmark.reference import resnet as ref

WHOLE_NUMBER_INPUTS = ()
LABEL_INPUTS = ("softmax_label",)


def _units(config):
    return tuple(int(u) for u in config["units"])


def _bn_names(config):
    names = ["bn_data", "bn0", "bn1"]
    for s, n in enumerate(_units(config), start=1):
        for u in range(1, n + 1):
            names += [f"stage{s}_unit{u}_bn{i}" for i in (1, 2, 3)]
    return names


def param_specs(config):
    """Leaves under the program's (MXNet's) names: convolutions He-normal
    on their fan-in, the classifier N(0, 0.01), batch-norm scales
    1 + N(0, 0.1) and shifts N(0, 0.1).  Norms at identity would make a
    scale followed by ReLU, a convolution and another norm a leaf whose
    true gradient is nought, and the comparison of such a leaf measures
    round-off alone; the input norm's scale is fixed at one by the
    model."""
    f = [int(x) for x in config["filters"]]
    specs = {}

    def conv(name, cout, cin, k):
        specs[name + "_weight"] = {
            "shape": [cout, cin, k, k], "init": "normal",
            "std": float(np.sqrt(2.0 / (cin * k * k)))}

    conv("conv0", f[0], 3, 7)
    cin = f[0]
    for s, n in enumerate(_units(config), start=1):
        cout, mid = f[s], f[s] // 4
        for u in range(1, n + 1):
            name = f"stage{s}_unit{u}"
            conv(name + "_conv1", mid, cin, 1)
            conv(name + "_conv2", mid, mid, 3)
            conv(name + "_conv3", cout, mid, 1)
            if u == 1:
                conv(name + "_sc", cout, cin, 1)
            cin = cout
    classes = int(config["num_classes"])
    specs["fc1_weight"] = {"shape": [classes, cin], "init": "normal",
                           "std": 0.01}
    specs["fc1_bias"] = {"shape": [classes], "init": "zeros"}
    widths = {"bn_data": 3, "bn0": f[0], "bn1": f[-1]}
    cin = f[0]
    for s, n in enumerate(_units(config), start=1):
        for u in range(1, n + 1):
            name = f"stage{s}_unit{u}"
            widths[name + "_bn1"] = cin
            widths[name + "_bn2"] = widths[name + "_bn3"] = f[s] // 4
            cin = f[s]
    aux = {}
    for bn in _bn_names(config):
        c = widths[bn]
        if bn == "bn_data":
            specs[bn + "_gamma"] = {"shape": [c], "init": "ones"}
        else:
            specs[bn + "_gamma"] = {"shape": [c], "init": "around_one",
                                    "std": 0.1}
        specs[bn + "_beta"] = {"shape": [c], "init": "normal", "std": 0.1}
        aux[bn + "_moving_mean"] = {"shape": [c], "init": "zeros"}
        aux[bn + "_moving_var"] = {"shape": [c], "init": "ones"}
    return specs, aux


# ---------------------------------------------------------------- program
def build_symbol(config, traffic):
    from mxnet_tpu import models

    size = int(config["image_size"])
    shape = (3, size, size)
    units, filters = list(_units(config)), [int(x) for x in config["filters"]]
    if (units, filters) == ([3, 4, 6, 3], [64, 256, 512, 1024, 2048]):
        return models.get_symbol("resnet-50",
                                 num_classes=int(config["num_classes"]),
                                 image_shape=shape)
    # the rehearsal's narrow, shallow network: the same generator
    return models.resnet.resnet(units, len(units), filters,
                                int(config["num_classes"]), shape,
                                bottle_neck=True)


def input_shapes(config, traffic):
    size = int(config["image_size"])
    return {"data": (int(traffic["batch"]), 3, size, size)}


def train_specs(config, traffic):
    return param_specs(config)


def host_batches(config, traffic, seed, n):
    """``n`` host batches: float32 images uniform in [0, 1) and labels
    uniform over the classes, all rows different."""
    rng = np.random.default_rng([int(seed), 1])
    b, size = int(traffic["batch"]), int(config["image_size"])
    return [{"data": rng.random((b, 3, size, size), dtype=np.float32),
             "softmax_label": rng.integers(
                 0, int(config["num_classes"]), b).astype(np.float32)}
            for _ in range(n)]


def labels_of(batch):
    return batch["softmax_label"]


def step_flops(config, traffic):
    macs = work.resnet_forward_macs(
        list(_units(config)), [int(x) for x in config["filters"]],
        int(config["image_size"]), int(config["num_classes"]))
    return 3 * 2 * macs * int(traffic["batch"])


def kernel_work(config, traffic):
    return {}       # no Pallas kernel in this step today


# -------------------------------------------------------------- reference
def reference_params(config, seed):
    import jax.numpy as jnp

    return weights.make(param_specs(config)[0], seed, jnp.float32)


def reference_grads(config, params, aux, batch, _rows, compute, keep=None):
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(batch["data"])
    y = jnp.asarray(batch["softmax_label"], jnp.int32)
    total = x.shape[0]
    if keep:
        x, y = x[:keep], y[:keep]
    loss, grads = ref.loss_and_grads(params, x, y, units=_units(config),
                                     compute=compute)
    if keep:
        grads = jax.tree_util.tree_map(lambda g: g * (total / keep), grads)
    return float(loss) / x.shape[0], grads, aux
