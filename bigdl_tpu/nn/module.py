"""Module system core: functional layers with a Torch-style imperative facade.

Reference contract: ``AbstractModule[A, B, T]``
(nn/abstractnn/AbstractModule.scala:59) -- every layer has mutable
``output``/``gradInput``, template methods ``updateOutput`` /
``updateGradInput`` / ``accGradParameters`` and a ``parameters()`` accessor.

TPU-native redesign: the *core* of every layer is a pair of pure functions

    setup(rng, input_spec)                  -> (params, state)
    apply(params, state, input, training, rng) -> (output, new_state)

``params`` / ``state`` are pytrees of jax Arrays; ``input``/``output`` are
activities (a single array or a nested tuple -- the analogue of the
reference's ``Activity = Tensor | Table``).  The backward pass is autodiff
(``jax.vjp``) instead of hand-written ``updateGradInput`` -- there is nothing
to hand-derive, and XLA fuses the whole step.

The imperative facade (``forward``/``backward``/``parameters``/
``zero_grad_parameters``/``training``/``evaluate``) reproduces the reference
API surface for tests and interactive use.  The hot path -- Local/Distri
optimizers -- never uses the facade: they extract ``setup``/``apply`` and jit
one fused train step (see optim/local_optimizer.py).
"""

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.utils.random_generator import RNG
from bigdl_tpu.utils.shape import spec_of, tree_add

Params = Any
State = Any
Activity = Any

_name_counters = {}


def _record_init(cls):
    """Wrap ``cls.__init__`` to record the constructor call on the instance.

    The outermost (most-derived) call wins; nested super().__init__ calls
    see ``_init_args`` already set and leave it alone.  This is the
    reflection seam the protobuf serializer uses to round-trip EVERY module
    without per-class converters (reference: ModuleSerializable's
    constructor-mirror reflection, utils/serializer/ModuleSerializable.scala).
    """
    orig = cls.__dict__["__init__"]

    @functools.wraps(orig)
    def __init__(self, *args, **kwargs):
        if not hasattr(self, "_init_args"):
            self._init_args = (args, dict(kwargs))
        orig(self, *args, **kwargs)

    cls.__init__ = __init__


def _install_pending_after_setup(cls):
    """Wrap ``cls.setup`` so arrays stored by set_weights /
    set_state_entries BEFORE build install into the freshly created
    params/state no matter who runs setup -- containers call child.setup
    directly (never child.build), so without this hook pending weights on
    nested unbuilt layers would be silently ignored."""
    orig = cls.__dict__["setup"]

    @functools.wraps(orig)
    def setup(self, rng, input_spec):
        p, s = orig(self, rng, input_spec)
        pw = getattr(self, "_pending_weights", None)
        if pw is not None:
            self._pending_weights = None
            self._install_weight_list(pw, tree=p)
        ps = getattr(self, "_pending_state", None)
        if ps is not None:
            self._pending_state = None
            self._install_state_entries(ps, tree=s)
        return p, s

    cls.setup = setup


class _Name(str):
    """Module name that is BOTH an attribute and callable.

    The reference exposes the name as a METHOD (pyspark Layer.name(),
    AbstractModule.getName), while this codebase reads ``module.name`` as
    a plain string everywhere; a callable str subclass satisfies both
    (``m.name`` and ``m.name()`` return the same string)."""

    def __call__(self) -> str:
        return str(self)


def _auto_name(cls_name: str) -> str:
    n = _name_counters.get(cls_name, 0)
    _name_counters[cls_name] = n + 1
    return f"{cls_name}{n}"


def child_rng(rng, index: int):
    """Deterministic per-child key derivation (traceable)."""
    if rng is None:
        return None
    return jax.random.fold_in(rng, index)


class Module:
    """Base class of every layer (reference: AbstractModule.scala:59)."""

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if "__init__" in cls.__dict__:
            _record_init(cls)
        if "setup" in cls.__dict__:
            _install_pending_after_setup(cls)

    def __init__(self, name: Optional[str] = None):
        self.name = name or _auto_name(type(self).__name__)
        self.train_mode: bool = True
        # facade state
        self.output: Activity = None
        self.grad_input: Activity = None
        self._params: Params = None
        self._state: State = None
        self._grads: Params = None
        self._last_rng = None
        self._build_spec = None

    @property
    def name(self) -> "_Name":
        return self._name

    @name.setter
    def name(self, value):
        # every assignment (constructors, deserializers, caffe importer)
        # funnels through here, so the name()-callable parity survives a
        # save/load round-trip
        self._name = _Name(value)

    # ------------------------------------------------------------------ #
    # Functional contract -- override these two in every layer.
    # ------------------------------------------------------------------ #
    def setup(self, rng, input_spec) -> Tuple[Params, State]:
        """Create (params, state) for the given abstract input spec."""
        return (), ()

    def apply(
        self, params: Params, state: State, input: Activity, *, training: bool = False,
        rng=None,
    ) -> Tuple[Activity, State]:
        raise NotImplementedError(type(self).__name__)

    def output_spec(self, params, state, input_spec, training: bool = False):
        out, _ = jax.eval_shape(
            lambda p, s, x: self.apply(p, s, x, training=training, rng=None),
            params, state, input_spec,
        )
        return out

    # ------------------------------------------------------------------ #
    # Imperative facade (reference API surface).
    # ------------------------------------------------------------------ #
    def is_built(self) -> bool:
        return self._params is not None or self._state is not None

    def build(self, input_spec, rng=None) -> "Module":
        """Materialise params/state for an input spec (lazy in forward())."""
        if rng is None:
            rng = RNG.next_key()
        self._build_spec = input_spec     # recorded for serialization
        self._params, self._state = self.setup(rng, input_spec)
        self._grads = None
        # pending set_weights/set_state_entries arrays are normally
        # installed by the setup wrapper (_install_pending_after_setup);
        # classes inheriting the base no-param setup are not wrapped, so
        # consume (and validate) any leftovers here
        pending = getattr(self, "_pending_weights", None)
        if pending is not None:
            self._pending_weights = None
            self._install_weight_list(pending)
        pending_state = getattr(self, "_pending_state", None)
        if pending_state is not None:
            self._pending_state = None
            self._install_state_entries(pending_state)
        return self

    # static loaders (reference: Scala `object Module` + pyspark
    # Model.load_torch/load_keras/load_caffe/load_caffe_model/
    # load_tensorflow, pyspark/bigdl/nn/layer.py:772-850)
    @staticmethod
    def load_torch(path):
        """Load a Torch .t7 serialized module."""
        from bigdl_tpu.utils.torch_file import load_torch_module

        return load_torch_module(path)

    @staticmethod
    def load_keras(json_path=None, hdf5_path=None, by_name=False):
        """Load a Keras JSON/HDF5 model definition (+weights)."""
        if by_name:
            raise NotImplementedError(
                "by_name weight matching is not supported; load the full "
                "topology (json_path) with its weights instead")
        from bigdl_tpu.keras.converter import load_keras

        return load_keras(json_path=json_path, hdf5_path=hdf5_path)

    @staticmethod
    def load_caffe(model, defPath, modelPath, match_all=True):
        """Copy caffe weights into an existing model (by layer name)."""
        from bigdl_tpu.interop.caffe import load

        return load(model, defPath, modelPath, match_all=match_all)

    @staticmethod
    def load_caffe_model(defPath, modelPath):
        """Build a model purely from a caffe prototxt + caffemodel."""
        from bigdl_tpu.interop.caffe import load_caffe

        return load_caffe(defPath, modelPath)

    @staticmethod
    def load_tensorflow(path, inputs, outputs, byte_order="little_endian",
                        bin_file=None):
        """Import a frozen TF GraphDef as a trainable module."""
        if byte_order != "little_endian":
            raise ValueError("only little_endian byte order is supported")
        if bin_file is not None:
            raise NotImplementedError(
                "separate dumped-weights bin_file is not supported; export "
                "a frozen GraphDef with the weights folded in")
        from bigdl_tpu.interop.tensorflow import load_tf

        return load_tf(path, inputs, outputs)

    def set_running_mean(self, running_mean) -> "Module":
        """Install a BatchNormalization running mean (reference: pyspark
        Layer.set_running_mean -> PythonBigDL.setRunningMean)."""
        return self.set_state_entries({"running_mean": running_mean})

    def set_running_std(self, running_std) -> "Module":
        """Install a BatchNormalization running VARIANCE -- the reference
        method is named *std* but stores into runningVar verbatim
        (PythonBigDL.scala:2731 setRunningStd -> module.runningVar.set);
        the naming quirk is kept for drop-in parity."""
        return self.set_state_entries({"running_var": running_std})

    def set_state_entries(self, entries):
        """Install {key: array} into the state pytree by leaf-dict key name
        (e.g. BN running_mean/running_var).  Before build, kept pending and
        installed when build() runs -- the state analogue of set_weights."""
        import numpy as np

        entries = {k: np.asarray(v, np.float32) for k, v in entries.items()}
        if not self.is_built():
            # MERGE: set_running_mean then set_running_std before build is
            # the normal pyspark pattern; overwriting would drop the first
            self._pending_state = {**(getattr(self, "_pending_state", None)
                                      or {}), **entries}
            return self
        return self._install_state_entries(entries)

    def _install_state_entries(self, entries, tree=None):
        hit = set()

        def walk(t):
            if isinstance(t, dict):
                for k in list(t):
                    if k in entries and hasattr(t[k], "shape"):
                        want = tuple(t[k].shape)
                        got = tuple(entries[k].shape)
                        if want != got:
                            raise ValueError(
                                f"set_state_entries: shape {got} != "
                                f"expected {want} for '{k}'")
                        t[k] = jnp.asarray(entries[k])
                        hit.add(k)
                    else:
                        walk(t[k])
            elif isinstance(t, (tuple, list)):
                for v in t:
                    walk(v)
        walk(self._state if tree is None else tree)
        missing = set(entries) - hit
        if missing:
            raise ValueError(f"set_state_entries: no state leaves named "
                             f"{sorted(missing)}")
        return self

    def _ensure_built(self, input: Activity):
        if not self.is_built():
            self.build(spec_of(input))

    def forward(self, input: Activity) -> Activity:
        """Reference: AbstractModule.forward (AbstractModule.scala:255)."""
        self._ensure_built(input)
        self._last_rng = RNG.next_key() if self.train_mode else None
        self.output, self._state = self.apply(
            self._params, self._state, input,
            training=self.train_mode, rng=self._last_rng,
        )
        return self.output

    def backward(self, input: Activity, grad_output: Activity) -> Activity:
        """updateGradInput + accGradParameters fused via jax.vjp.

        Reference: AbstractModule.backward (AbstractModule.scala:282).
        Gradients accumulate into the module until zero_grad_parameters(),
        matching accGradParameters semantics.
        """
        self._ensure_built(input)
        rng, training = self._last_rng, self.train_mode

        def f(p, x):
            y, _ = self.apply(p, self._state, x, training=training, rng=rng)
            return y

        _, vjp = jax.vjp(f, self._params, input)
        gparams, ginput = vjp(grad_output)
        self._grads = tree_add(self._grads, gparams)
        self.grad_input = ginput
        return ginput

    def parameters(self) -> Tuple[Params, Params]:
        """(weights, gradWeights) pytrees (reference: parameters(), :347)."""
        if self._grads is None and self._params is not None:
            self._grads = jax.tree.map(jnp.zeros_like, self._params)
        return self._params, self._grads

    def weights(self) -> Params:
        """The weights alone.  ``parameters()`` also hands out the facade's
        gradient tree and allocates it, zeros as large as the weights, the
        first time it is asked: a path that only reads weights (serving)
        asks here and holds no such tree."""
        return self._params

    def set_parameters(self, params: Params):
        self._params = params

    # weight-list accessors (reference: Layer.get_weights/set_weights in
    # pyspark/bigdl/nn/layer.py:478-508 -- flat [weight, bias, ...] arrays
    # in layer traversal order)
    def _weight_leaves(self, tree=None):
        """[(dict, key)] of param leaves, weight-before-bias per dict."""
        order = {"weight": 0, "bias": 1}
        found = []

        def walk(t):
            if isinstance(t, dict):
                for k in sorted(t, key=lambda k: (order.get(k, 2), k)):
                    v = t[k]
                    if isinstance(v, (dict, tuple, list)):
                        walk(v)
                    elif hasattr(v, "shape"):
                        found.append((t, k))
            elif isinstance(t, (tuple, list)):
                for v in t:
                    walk(v)
        walk(self._params if tree is None else tree)
        return found

    def get_weights(self):
        if not self.is_built():
            return []
        import numpy as np

        return [np.asarray(d[k]) for d, k in self._weight_leaves()]

    def set_weights(self, weights):
        """Install a flat weight list.  Before build, the arrays are kept
        pending and installed when build() runs (the pyspark API sets
        weights on eagerly-constructed layers)."""
        import numpy as np

        if not self.is_built():
            self._pending_weights = [np.asarray(w) for w in weights]
            return self
        return self._install_weight_list(weights)

    def _install_weight_list(self, weights, tree=None):
        leaves = self._weight_leaves(tree)
        if len(leaves) != len(weights):
            raise ValueError(
                f"set_weights: {len(weights)} arrays for {len(leaves)} "
                f"parameter tensors")
        import numpy as np

        # the (dict, key) handles returned above are the live dicts
        for (d, k), w in zip(leaves, weights):
            w = np.asarray(w, np.float32)
            want = tuple(d[k].shape)
            if w.shape != want:
                raise ValueError(
                    f"set_weights: shape {w.shape} != expected {want} "
                    f"for '{k}'")
            d[k] = jnp.asarray(w)
        return self

    def get_parameters(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Flat (weights, grads) 1-D views (reference: getParameters).

        Unlike the reference there is no storage aliasing -- these are packed
        copies (SURVEY.md: don't replicate strided aliasing).
        """
        from jax.flatten_util import ravel_pytree

        p, g = self.parameters()
        flat_p, _ = ravel_pytree(p)
        flat_g, _ = ravel_pytree(g)
        return flat_p, flat_g

    def zero_grad_parameters(self):
        if self._params is not None:
            self._grads = jax.tree.map(jnp.zeros_like, self._params)

    def update_parameters(self, learning_rate: float):
        """In-place ``p -= lr * gradP`` over the accumulated facade
        gradients (reference: AbstractModule.updateParameters /
        pyspark Layer.update_parameters)."""
        if self._params is None:
            raise ValueError("update_parameters() before build()")
        params, grads = self.parameters()
        self._params = jax.tree.map(
            lambda p, g: p - learning_rate * g, params, grads)
        return self

    def reset(self):
        """Re-initialise weights from the recorded build spec with a fresh
        RNG draw (reference: AbstractModule.reset)."""
        if self._build_spec is None:
            raise ValueError("reset() before build()")
        return self.build(self._build_spec)

    def set_name(self, name: str) -> "Module":
        """Reference: pyspark Layer.set_name (also AbstractModule.setName)."""
        self.name = _Name(name)
        return self

    def set_seed(self, seed: int = 123) -> "Module":
        """Seed the global init RNG (reference: pyspark Layer.set_seed ->
        RandomGenerator.RNG.setSeed)."""
        RNG.set_seed(seed)
        return self

    def is_training(self) -> bool:
        return self.train_mode

    def is_with_weights(self) -> bool:
        """Whether this (built) module carries any weights
        (reference: pyspark Layer.is_with_weights)."""
        return self._params is not None and bool(jax.tree.leaves(self._params))

    def freeze(self, names=None) -> "Module":
        """Stop parameter updates (reference: AbstractModule.freeze /
        pyspark Layer.freeze).  With ``names``, freezes the matching
        descendant modules; without, freezes this whole module.  Honored
        by ``make_train_step`` (gradients zeroed AND parameters restored
        after the optimizer update, so weight decay cannot leak in)."""
        if names is None:
            self._frozen = True
        else:
            self._freeze_named(set(names), True)
        return self

    def unfreeze(self, names=None) -> "Module":
        """With ``names``, explicitly marks those modules trainable — this
        OVERRIDES a frozen ancestor (tri-state: True=frozen, False=pinned
        trainable, unset=inherit), matching the reference's
        freeze-all-then-unfreeze-the-head fine-tune pattern.  Without
        ``names``, clears every mark below (and on) this module."""
        if names is None:
            self._frozen = None
            for m in self.children():
                m.unfreeze()
        else:
            self._freeze_named(set(names), False)
        return self

    def _freeze_named(self, names, value):
        found = []

        def walk(m):
            if str(m.name) in names:
                m._frozen = value
                found.append(str(m.name))
            for c in m.children():
                walk(c)

        walk(self)
        missing = names - set(found)
        if missing:
            raise ValueError(f"freeze: no modules named {sorted(missing)}")

    def _param_child_items(self, params):
        """[(params key, child module)] aligning this container's params
        dict with its children for the frozen-mask walk.  Sequential-style
        containers key children by index; Graph/MapTable override."""
        return [(str(i), c) for i, c in enumerate(self.children())]

    def training(self) -> "Module":
        self.train_mode = True
        for m in self.children():
            m.training()
        return self

    def evaluate(self) -> "Module":
        self.train_mode = False
        for m in self.children():
            m.evaluate()
        return self

    def quantize(self) -> "Module":
        """Rewrite this built model for int8 inference (reference:
        AbstractModule.scala:919 ``quantize()`` -> Quantizer): Linear and
        convolution layers swap to their int8 twins with weights
        quantized in place; returns self in eval mode."""
        from bigdl_tpu.nn.quantized import quantize as _quantize
        return _quantize(self)

    def set_regularizer(self, w=None, b=None, u=None):
        """Attach per-layer weight/bias/recurrent regularizers (reference:
        wRegularizer/bRegularizer/uRegularizer params on layer
        constructors, optim/Regularizer.scala).  Consumed by the train
        step's loss; ``u`` applies to recurrent (hidden-to-hidden)
        weights -- param keys named weight_hh."""
        if w is not None:
            self.w_regularizer = w
        if b is not None:
            self.b_regularizer = b
        if u is not None:
            self.u_regularizer = u
        return self

    def children(self):
        return []

    def state(self) -> State:
        return self._state

    def set_state(self, state: State):
        self._state = state

    def save(self, path: str):
        """Persist architecture + weights (reference: AbstractModule.save /
        saveModule)."""
        from bigdl_tpu.utils.serializer import save_module

        save_module(self, path)
        return self

    @staticmethod
    def load(path: str) -> "Module":
        """Reference: Module.load / ModuleLoader.loadFromFile."""
        from bigdl_tpu.utils.serializer import load_module

        return load_module(path)

    def save_weights(self, path: str):
        from bigdl_tpu.utils.serializer import save_weights

        save_weights(self, path)
        return self

    def load_weights(self, path: str):
        from bigdl_tpu.utils.serializer import load_weights

        return load_weights(self, path)

    def predict(self, data, batch_size: int = 128):
        """Batch inference sugar (reference: AbstractModule.predict :637)."""
        from bigdl_tpu.optim.predictor import Predictor

        return Predictor(self, batch_size).predict(data)

    def predict_class(self, data, batch_size: int = 128):
        from bigdl_tpu.optim.predictor import Predictor

        return Predictor(self, batch_size).predict_class(data)

    # pyspark Layer facade spellings (reference: pyspark/bigdl/nn/layer.py
    # predict_local :372 / predict_distributed :426 and the _class
    # variants).  The Predictor behind predict() already consumes local
    # arrays, Samples, DataSets AND partitioned sources, so local /
    # distributed collapse to the same call here.
    def predict_local(self, X, batch_size: int = 128):
        import numpy as np

        return np.stack(self.predict(X, batch_size))

    def predict_class_local(self, X, batch_size: int = 128):
        import numpy as np

        return np.asarray(self.predict_class(X, batch_size))

    predict_distributed = predict
    predict_class_distributed = predict_class

    def predict_image(self, image_frame, output_layer=None,
                      share_buffer=False, batch_per_partition=4,
                      predict_key="predict"):
        """Run inference over an ImageFrame, storing each output under
        ``predict_key`` on its ImageFeature (reference: pyspark
        Layer.predict_image :451 -> ImageFrame predict).  ``output_layer``
        / ``share_buffer`` are JVM execution details with no analogue
        here (one fused XLA program; buffers are XLA-owned)."""
        samples = image_frame.to_samples()
        outs = self.predict(samples, batch_size=batch_per_partition)
        for feature, out in zip(image_frame.features, outs):
            feature[predict_key] = out
        return image_frame

    def save_caffe(self, prototxt_path, model_path, use_v2=True,
                   overwrite=False):
        """Reference: pyspark Layer.save_caffe -> CaffePersister.  The
        input shape comes from the recorded build spec."""
        import os as _os

        if self._build_spec is None:
            raise ValueError("save_caffe() requires a built model")
        if not overwrite and (_os.path.exists(prototxt_path)
                              or _os.path.exists(model_path)):
            raise FileExistsError(
                f"{prototxt_path} / {model_path} exist (overwrite=False)")
        from bigdl_tpu.interop.caffe import save_caffe as _save

        shape = getattr(self._build_spec, "shape", None)
        _save(self, prototxt_path, model_path, shape)
        return self

    def save_tensorflow(self, inputs, path, byte_order="little_endian",
                        data_format="nhwc"):
        """Reference: pyspark Layer.save_tensorflow -> TensorflowSaver.
        ``inputs`` is the reference's [(name, shape)] list; the first
        entry names the graph input."""
        if byte_order != "little_endian":
            raise ValueError("only little_endian byte order is supported")
        if data_format != "nhwc":
            raise ValueError("exported graphs are NHWC (TPU-native layout)")
        from bigdl_tpu.interop.tensorflow import save_tf

        (input_name, input_shape) = inputs[0]
        save_tf(self, path, tuple(input_shape), input_name=input_name)
        return self

    def evaluate_on(self, dataset, methods, compute_dtype=None):
        """Run validation methods over a dataset
        (reference: AbstractModule.evaluate :855; named evaluate_on because
        evaluate() toggles eval mode, as in the reference)."""
        from bigdl_tpu.optim.predictor import evaluate

        return evaluate(self, dataset, methods, compute_dtype)

    # Graph building: calling a module on Node(s) creates a new graph node
    # (reference: ModuleNode / Graph, nn/Graph.scala:72).
    def __call__(self, *args):
        from bigdl_tpu.nn.graph import Node

        if args and all(isinstance(a, Node) for a in args):
            return Node(self, list(args))
        if len(args) == 1:
            return self.forward(args[0])
        raise TypeError(
            "Module(...) expects graph Nodes (to build a Graph) or a single "
            "activity (to run forward)."
        )

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


class Container(Module):
    """Base for modules that own sub-modules (reference: nn/Container.scala:40)."""

    def __init__(self, name=None):
        super().__init__(name)
        self.modules = []

    def add(self, module: Module) -> "Container":
        self.modules.append(module)
        return self

    def children(self):
        return list(self.modules)

    def training(self):
        self.train_mode = True
        for m in self.modules:
            m.training()
        return self

    def evaluate(self):
        self.train_mode = False
        for m in self.modules:
            m.evaluate()
        return self


def has_frozen(module: Module) -> bool:
    """True if this module or any descendant was froze()n."""
    if getattr(module, "_frozen", None) is True:
        return True
    return any(has_frozen(c) for c in module.children())


def frozen_param_mask(module: Module, params=None):
    """Pytree parallel to ``params`` with a python-bool leaf per array:
    True = trainable, False = under a frozen module.

    Alignment of param subtrees to child modules goes through each
    container's ``_param_child_items`` (Sequential-style containers key
    by child index; Graph keys by topo index; MapTable's params ARE the
    shared child's), so freeze() works on every container family.  The
    frozen mark is tri-state: an explicit ``unfreeze(names)`` (False)
    overrides a frozen ancestor.  Static (python bools), so using the
    mask inside a jitted step costs nothing at runtime.
    """
    if params is None:
        params = module.parameters()[0]

    def walk(m, p, inherited):
        own = getattr(m, "_frozen", None)
        frozen = inherited if own is None else own
        items = m._param_child_items(p)
        if len(items) == 1 and items[0][0] is None:
            # the whole subtree belongs to one shared child (MapTable)
            return walk(items[0][1], p, frozen)
        if items and isinstance(p, dict):
            by_key = dict(items)
            out = {}
            for k in p:
                if k in by_key:
                    out[k] = walk(by_key[k], p[k], frozen)
                else:
                    out[k] = jax.tree.map(lambda _: not frozen, p[k])
            return out
        return jax.tree.map(lambda _: not frozen, p)

    return walk(module, params, False)


class Criterion:
    """Loss base (reference: AbstractCriterion.scala).

    Core: pure ``apply(input, target) -> scalar loss``.  Facade ``forward`` /
    ``backward`` mirror the reference; backward is ``jax.grad`` wrt input.
    """

    size_average: bool = True

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if "__init__" in cls.__dict__:
            _record_init(cls)

    def apply(self, input: Activity, target: Activity) -> jnp.ndarray:
        raise NotImplementedError(type(self).__name__)

    def forward(self, input: Activity, target: Activity):
        self.output = self.apply(input, target)
        return self.output

    def backward(self, input: Activity, target: Activity):
        self.grad_input = jax.grad(lambda x: self.apply(x, target))(input)
        return self.grad_input

    def __call__(self, input, target):
        return self.forward(input, target)


class Identity(Module):
    """Reference: nn/Identity.scala."""

    def apply(self, params, state, input, *, training=False, rng=None):
        return input, state
