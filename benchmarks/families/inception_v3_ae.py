"""Family `inception_v3_ae`: upstream's Inception-v3 example (with 1000
classes, see the configuration file's departures), built through `create_inception_v3` + `FFModel.compile`. See `bert_ae.py` for
what a family gives the harness.
"""

import math

import numpy as np

from benchmarks.references import inception_v3_ae as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"). (a) pred_log_nrmse: RMS error of the log-probabilities over the
# reference's standard deviation: program 0.0085-0.0138 over 12 seeds,
# float8 control 0.0675-0.082. (b) loss0_rel: program at most 0.0024 over
# 24 seeds. (c) later_loss_rel: program at most 0.0023, Adam without bias
# correction 0.051-0.109.
TOLERANCES = {"pred_log_nrmse": 3.0e-2, "loss0_rel": 1.0e-2,
              "later_loss_rel": 1.0e-2}
PREDICTIONS_ARE_PROBABILITIES = True   # compared as log-probabilities


def sizes(config, traffic, overrides=None):
    s = {k: config[k] for k in ("image_size", "num_classes")}
    s.update(batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    return s


def make_data(s, seed):
    rng = np.random.default_rng(seed)
    n = s["batch"] * s["steps_per_epoch"]
    x = rng.standard_normal((n, 3, s["image_size"], s["image_size"]),
                            dtype=np.float32)
    y = rng.integers(0, s["num_classes"], size=(n, 1), dtype=np.int32)
    return [x], y


def make_weights(s, seed):
    """Convolution kernels uniform with variance gain / fan_in (gain 2
    before a ReLU, 1 otherwise; fan_in = cin * kh * kw), so that
    activations keep their scale through the 47 convolutions of the
    longest path and the class probabilities carry information; the
    classifier Glorot uniform; biases zero. One jitted call."""
    import jax
    import jax.numpy as jnp

    arch = reference_module.shapes(s["image_size"], s["num_classes"])

    def init(key):
        convs = []
        for cout, cin, kh, kw, _, _, relu in arch.convs:
            key, sub = jax.random.split(key)
            limit = math.sqrt(3.0 * (2.0 if relu else 1.0) / (cin * kh * kw))
            convs.append({
                "kernel": jax.random.uniform(sub, (cout, cin, kh, kw),
                                             jnp.float32, -limit, limit),
                "bias": jnp.zeros((cout,), jnp.float32)})
        key, sub = jax.random.split(key)
        fin, fout = arch.fc
        limit = math.sqrt(6.0 / (fin + fout))
        fc = {"kernel": jax.random.uniform(sub, (fin, fout), jnp.float32,
                                           -limit, limit),
              "bias": jnp.zeros((fout,), jnp.float32)}
        return {"convs": convs, "fc": fc}

    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType, MetricsType
    from flexflow_tpu.models import InceptionConfig, create_inception_v3

    ic = InceptionConfig(batch_size=s["batch"], image_size=s["image_size"],
                         num_classes=s["num_classes"], reduced=False)
    cfg = FFConfig(batch_size=s["batch"], workers_per_node=chips,
                   seed=seed % (2 ** 31 - 1),
                   search_budget=config["search_budget"],
                   enable_parameter_parallel=chips > 1)
    ff = create_inception_v3(ic, cfg)
    adam = config["adam"]
    ff.compile(AdamOptimizer(alpha=adam["alpha"], beta1=adam["beta1"],
                             beta2=adam["beta2"], epsilon=adam["epsilon"],
                             weight_decay=adam["weight_decay"],
                             state_dtype=jnp.dtype(adam["state_dtype"])),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.ACCURACY,
                MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
               machine_spec=machine_spec)
    return ff


def _layer_names(ff):
    from flexflow_tpu.ffconst import OperatorType
    convs = [l.name for l in ff.layers if l.op_type == OperatorType.CONV2D]
    (fc,) = [l.name for l in ff.layers if l.op_type == OperatorType.LINEAR]
    return convs, fc


def install_weights(ff, weights):
    """The reference numbers convolutions in creation order; so does the
    program's layer list."""
    convs, fc = _layer_names(ff)
    if len(convs) != len(weights["convs"]):
        raise AssertionError(f"{len(convs)} convolutions in the program, "
                             f"{len(weights['convs'])} in the reference")
    for name, leaves in zip(convs + [fc], weights["convs"] + [weights["fc"]]):
        for pname, value in leaves.items():
            ff.set_parameter(name, value, pname)


def readback(ff, weights):
    convs, _ = _layer_names(ff)
    return (np.asarray(ff.get_parameter(convs[-1], "kernel")),
            np.asarray(weights["convs"][-1]["kernel"]))


def reference(s, traffic):
    return (reference_module, dict(num_classes=s["num_classes"]),
            traffic.get("reference_chunk", 16))


def train_flops_per_sample(s):
    """Forward and backward of one image: three times the multiply-adds
    of the convolutions and the classifier, times 2."""
    arch = reference_module.shapes(s["image_size"], s["num_classes"])
    fwd = sum(2 * cout * cin * kh * kw * oh * ow
              for cout, cin, kh, kw, oh, ow, _ in arch.convs)
    fwd += 2 * arch.fc[0] * arch.fc[1]
    return 3 * fwd


def extra_checks(ff, s, chips, on_tpu):
    info = getattr(ff, "layout_info", None) or {}
    if on_tpu:
        # the TPU default conv layout is channels-last
        return [("conv_layout_nhwc", bool(info.get("enabled")), info)]
    return []


def kernel_fallbacks(ff):
    return {}
