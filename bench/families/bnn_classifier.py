"""Serving of the secure binarized CNN classifiers (``"family":
"bnn_classifier"`` in a configuration file).

It drives the program's served path and nothing else:
``repro.launch.serve_secure.build`` turns the weights into the secure model,
``make_runner(model, layout, batch)`` gives the inline runner, and
``make_tape_runner`` with a ``repro.core.preprocessing.TapePool`` gives the
pool runner.  What the benchmark makes itself, from seeds:

* the weights, from the configuration's ``weights.seed``: integers on a
  grid of ``1/grid`` (a rounded standard normal, clipped to
  ``+-levels``), biases on the same grid plus ``bias_offset``, and
  batch-norm statistics that make each ``bn`` the identity.  With +-0.5
  pixels and +-1 activations, every pre-activation is a multiple of
  1/128 plus ``bias_offset`` (1/256), so it sits 1/256 or more from the
  Sign boundary, far outside the fixed-point error of the 12-bit
  fraction: the secure run and the plain forward make the same Sign
  decisions, and their logits agree to a few units of 2^-12.  The seed is
  fixed per configuration because the served runner bakes the weights into
  its executable: weights that changed with ``--seed`` would make every
  run compile anew.
* the queries, from ``--seed``: ``distinct_batches`` batches of +-0.5
  pixels, secret-shared by the querier with the program's ``share``, and
  a fresh set of party keys for every query of the window.
"""
import numpy as np

WINDOW_KEYS = 8192


def _seed_words(seed, n):
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)


def make_params(layers, input_shape, spec):
    """Grid weights for ``layers`` in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    shapes = {}
    h, w, c = input_shape
    flat = None
    for i, l in enumerate(layers):
        kind = l["kind"]
        if kind in ("conv", "sepconv"):
            k, s, p = l.get("k", 3), l.get("stride", 1), l.get("pad", 0)
            if kind == "conv":
                shapes[f"l{i}_w"] = (k, k, c, l["out"])
            else:
                shapes[f"l{i}_dw"] = (k, k, 1, c)
                shapes[f"l{i}_pw"] = (1, 1, c, l["out"])
            shapes[f"l{i}_b"] = (l["out"],)
            h, w, c = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1, \
                l["out"]
        elif kind == "fc":
            cin = flat if flat is not None else h * w * c
            shapes[f"l{i}_w"] = (cin, l["out"])
            shapes[f"l{i}_b"] = (l["out"],)
            flat = l["out"]
        elif kind == "bn":
            ch = flat if flat is not None else c
            for name in ("g", "beta", "mu", "var"):
                shapes[f"l{i}_{name}"] = (ch,)
        elif kind == "maxpool":
            h, w = h // 2, w // 2
        elif kind == "flatten":
            flat = h * w * c

    grid, levels = float(spec["grid"]), float(spec["levels"])
    offset = float(spec["bias_offset"])

    @jax.jit
    def make(key):
        out = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith("_var"):
                out[name] = jnp.full(shape, 1.0 - 1e-5, jnp.float32)
            elif name.endswith(("_mu", "_beta")):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif name.endswith("_g"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                z = jax.random.normal(jax.random.fold_in(key, j), shape)
                v = jnp.clip(jnp.round(z), -levels, levels) / grid
                out[name] = v + offset if name.endswith("_b") else v
        return out

    return make(jax.random.PRNGKey(int(spec["seed"])))


def make_images(key, n_batches, batch, input_shape):
    """(n_batches, batch, H, W, C) pixels of +-0.5, on the device."""
    import jax
    import jax.numpy as jnp
    bits = jax.random.bernoulli(key, 0.5, (n_batches, batch) + input_shape)
    return jnp.where(bits, 0.5, -0.5).astype(jnp.float32)


class Serving:
    """One cell: ``build`` (seed-independent), ``load(seed)``, ``query(q)``
    for the warm-up and the window, then ``release`` and ``reference``."""

    def __init__(self, config, traffic, mark=None):
        self.config = config
        self.traffic = traffic
        self.batch = int(traffic["batch"])
        self.input_shape = tuple(config["input_shape"])
        self.pool = traffic["offline"] == "pool"
        self.mark = mark            # host span writer from the harness

    def _span(self, name):
        from contextlib import nullcontext
        return self.mark(name) if self.mark else nullcontext()

    # -- set-up -----------------------------------------------------------
    def check_program(self):
        """The program's net must be the configuration's layer list."""
        from repro.nn import bnn
        net = bnn.ALL_NETS[self.config["net"]]
        prog = [{"kind": l.kind, "out": l.out, "k": l.k, "stride": l.stride,
                 "pad": l.pad, "act": l.act} for l in net]
        mine = [dict({"out": 0, "k": 3, "stride": 1, "pad": 0,
                      "act": "sign"}, **l) for l in self.config["layers"]]
        if prog != mine or tuple(bnn.INPUT_SHAPES[self.config["net"]]) \
                != self.input_shape:
            raise SystemExit(f"{self.config['net']} in the program differs "
                             "from the configuration's layer list")

    def build(self):
        """Weights, secure model, runner; compiles nothing yet."""
        import jax
        from repro.core.preprocessing import (make_tape_generator,
                                              trace_material)
        from repro.launch.serve_secure import (build, make_runner,
                                               make_tape_runner)
        self.check_program()
        with self._span("bench.weights"):
            self.params = jax.block_until_ready(make_params(
                self.config["layers"], self.input_shape,
                self.config["weights"]))
        with self._span("bench.build"):
            self.model = build(self.config["net"], True,
                               self.config["weights"]["mode"],
                               params=self.params)
            jax.block_until_ready([op.get("w") for op in self.model.ops])
        ring = self.model.ring
        if (ring.bits, ring.frac) != (self.config["ring"]["bits"],
                                      self.config["ring"]["frac"]):
            raise SystemExit(f"the program serves Z_2^{ring.bits} with "
                             f"{ring.frac} fractional bits, not the "
                             f"configuration's {self.config['ring']}")
        layout = self.config["layout"]
        if self.pool:
            self.spec = trace_material(
                self.model, (self.batch,) + self.input_shape)
            self.gen = make_tape_generator(self.spec)
            self.run, self.prepare, _ = make_tape_runner(
                self.model, self.spec, layout)
        else:
            self.run, _ = make_runner(self.model, layout, self.batch)

    def load_images(self, seed):
        """The pixels of ``seed``'s distinct batches, on the device."""
        import jax
        words = _seed_words(seed, 4)
        n = int(self.traffic["distinct_batches"])
        make = jax.jit(make_images, static_argnums=(1, 2, 3))
        self.images = jax.block_until_ready(make(
            jax.random.PRNGKey(int(words[0])), n, self.batch,
            self.input_shape))
        return words

    def load(self, seed):
        """Queries of ``seed``: images, their shares, per-query keys, and
        in pool cells a filled tape pool."""
        import jax
        from repro.core import RING32, share
        with self._span("bench.queries"):
            words = self.load_images(seed)
            keys = jax.random.split(jax.random.PRNGKey(int(words[1])),
                                    self.images.shape[0])
            shares = jax.block_until_ready(jax.jit(jax.vmap(
                lambda x, k: share(x, k, RING32).shares))(self.images, keys))
            self.xs = [shares[i] for i in range(shares.shape[0])]
            rng = np.random.default_rng(int(words[2]))
            self.keys = rng.integers(0, 2 ** 32, (WINDOW_KEYS, 3, 2),
                                     dtype=np.uint32)
            if self.pool:
                from repro.core.preprocessing import TapePool
                self.tapes = TapePool(
                    self.gen, self.spec, int(self.traffic["pool_depth"]),
                    jax.random.PRNGKey(int(words[3])), demand=None)

    def query(self, q):
        """Serve query ``q``; returns (logits on the host, online seconds).
        Online seconds cover dispatch to logits on the host; in pool cells
        the staging of the tape slice before it is outside them."""
        import time
        import jax
        xs = self.xs[self.batch_of(q)]
        keys = jax.device_put(self.keys[q % WINDOW_KEYS])
        if self.pool:
            with self._span("bench.stage"):
                prepared = self.prepare(xs, self.tapes.take())
                jax.block_until_ready(prepared)
            t0 = time.perf_counter()
            with self._span("bench.dispatch"):
                out = self.run(keys, prepared)
        else:
            t0 = time.perf_counter()
            with self._span("bench.dispatch"):
                out = self.run(keys, xs)
        with self._span("bench.block"):
            out = np.asarray(jax.device_get(out))
        return out, time.perf_counter() - t0

    def batch_of(self, q):
        return q % int(self.traffic["distinct_batches"])

    # -- after the window --------------------------------------------------
    def release(self):
        """Free the program's state before the reference runs."""
        import gc
        for name in ("run", "prepare", "model", "tapes", "gen", "spec",
                     "xs"):
            self.__dict__.pop(name, None)
        gc.collect()

    def reference(self, ref_forward, dtype="float32"):
        """Reference logits of every distinct batch, one batch at a time."""
        import jax
        fwd = jax.jit(lambda p, x: ref_forward(p, x, self.config["layers"],
                                               dtype))
        return [np.asarray(fwd(self.params, self.images[i]))
                for i in range(self.images.shape[0])]
