"""AnalysisPredictor equivalent.

Ref ``AnalysisPredictor`` (``paddle/fluid/inference/api/analysis_predictor.h:95``):
``ZeroCopyRun`` (``:182``), input/output handles (``GetInputTensor``), the
``PredictorPool`` (``api/paddle_inference_api.h``) and ``Clone``.

TPU-native execution: the loaded artifact is a StableHLO program
(``jax.export``); a ``jax.jit`` wrapper is the NaiveExecutor+engine — first
``run()`` compiles (and caches, incl. persistently via
``Config.set_optim_cache_dir``), later runs replay the executable.
Weights stay resident on device; feeds move H2D on ``copy_from_cpu``;
outputs stay on device until ``copy_to_cpu`` — the ZeroCopy contract.
"""

from __future__ import annotations

import io as _io
import json
import pickle
import zipfile
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config

_JIT_MAGIC = "paddle_hackathon_tpu.jit.v1"


def get_version() -> str:
    from .. import __version__
    return __version__


class Tensor:
    """Zero-copy input/output handle (ref ``ZeroCopyTensor``
    ``paddle/fluid/inference/api/details/zero_copy_tensor.cc``)."""

    def __init__(self, name: str, device):
        self.name = name
        self._device = device
        self._value = None  # jax.Array on the target device
        self._shape_hint = None

    # -- input side --------------------------------------------------------
    def reshape(self, shape):
        """Declare the expected shape; validated on the next bind (shapes
        are otherwise taken from the bound array at run time)."""
        self._shape_hint = tuple(int(d) for d in shape)

    def copy_from_cpu(self, arr):
        arr = np.asarray(arr)
        hint = self._shape_hint
        if hint is not None and tuple(arr.shape) != hint:
            raise ValueError(
                f"tensor '{self.name}': bound array shape {arr.shape} does "
                f"not match reshape({list(hint)})")
        self._value = jax.device_put(arr, self._device)

    def share_external_data(self, tensor):
        """Bind an already-on-device array without a copy."""
        val = getattr(tensor, "_value", tensor)
        self._value = val

    # -- output side -------------------------------------------------------
    def copy_to_cpu(self):
        if self._value is None:
            raise RuntimeError(f"tensor '{self.name}' has no data; run() first")
        return np.asarray(self._value)

    def shape(self):
        return list(self._value.shape) if self._value is not None else []

    def type(self):
        return self._value.dtype if self._value is not None else None


class _BuildCtx:
    """Mutable context the pass pipeline operates on."""

    def __init__(self, config: Config):
        self.config = config
        self.donate_feeds = False
        self.resident_params = False


def _load_artifact(config: Config):
    """Load a static artifact (prefix.pdmodel raw StableHLO +
    prefix.pdiparams pickle), a jit zip artifact (MAGIC member), or a
    ``save_for_serving`` directory ({config.json, params.npz} — bf16 or
    weight-only-quantized; the quantized artifact rebuilds with fused
    dequant-GEMM Linears, so Predictor serves int8/fp8 weights through
    the same ZeroCopy interface)."""
    import os
    prog = config.prog_file()
    if prog is None:
        raise ValueError("Config has no model file; call set_model()")
    if os.path.isdir(prog) and os.path.exists(
            os.path.join(prog, "config.json")):
        from .serving import load_for_serving
        model = load_for_serving(prog)
        params, bufs = model.functional_state()
        return ("serving", model, params, bufs, ["input_ids"], 1)
    path = prog if prog.endswith(".pdmodel") else prog + ".pdmodel"
    if zipfile.is_zipfile(path):
        with zipfile.ZipFile(path, "r") as zf:
            names = zf.namelist()
            if "MAGIC" not in names or zf.read("MAGIC").decode() != _JIT_MAGIC:
                raise ValueError(
                    f"not a jit inference artifact: {path} (missing or "
                    f"unsupported MAGIC; expected {_JIT_MAGIC!r})")
            exported = jax.export.deserialize(zf.read("program.stablehlo"))
            meta = json.loads(zf.read("meta.json"))
            npz = np.load(_io.BytesIO(zf.read("params.npz")))
            params = [npz[f"p{i}"] for i in range(meta["n_params"])]
            buffers = [npz[f"b{i}"] for i in range(meta["n_buffers"])]
            feed_names = [f"x{i}" for i in range(len(meta["input_specs"]))]
            # out tree is (outputs..., new_buffers...): recover the
            # user-visible output count from the exported signature so
            # get_output_names() is correct before the first run()
            n_out = len(exported.out_avals) - meta["n_buffers"]
            return ("jit", exported, params, buffers, feed_names, n_out)
    with open(path, "rb") as f:
        exported = jax.export.deserialize(f.read())
    params_path = config.params_file()
    if params_path is None:
        prefix = path[:-len(".pdmodel")]
        params_path = prefix + ".pdiparams"
    with open(params_path, "rb") as f:
        meta = pickle.load(f)
    return ("static", exported, meta["params"], None, meta["feed_names"],
            meta["fetch_count"])


class Predictor:
    """Ref ``AnalysisPredictor`` (``analysis_predictor.h:95``)."""

    def __init__(self, config: Config, _shared=None):
        self._config = config
        ctx = _BuildCtx(config)
        if config.ir_optim():
            config.pass_builder().apply(ctx)
        self._ctx = ctx

        # "gpu" = the accelerator = the platform this process's JAX was
        # pointed at.  A process pointed at the chip that cannot reach it
        # fails in JAX's backend init — no quiet "any device" fallback;
        # JAX_PLATFORMS=cpu / disable_gpu() are the explicit CPU choices.
        devs = jax.devices() if config.use_gpu() else jax.devices("cpu")
        self._device = devs[min(config.gpu_device_id(), len(devs) - 1)]

        if _shared is not None:  # Clone(): share weights + executable
            (self._kind, self._exported, self._params, self._bufs,
             feed_names, self._fetch_count, self._compiled) = _shared
        else:
            (self._kind, self._exported, params, bufs, feed_names,
             self._fetch_count) = _load_artifact(config)
            if self._ctx.resident_params:
                # ZeroCopy weights: pinned on the target device once
                put = (lambda a: jax.device_put(jnp.asarray(a), self._device))
            else:
                # pass is disabled (or ir_optim off): weights stay on host
                # and transfer on each run
                put = np.asarray
            if self._kind == "serving":
                # the live model already holds these arrays (run_fn
                # closes over it) — tree-mapping a put here would keep a
                # SECOND full weight copy alive for the Predictor's
                # lifetime, doubling the footprint the quantized
                # artifact exists to halve
                self._params, self._bufs = params, bufs
            else:
                # list-shaped pdmodel/jit artifacts: resident-params
                # pins to the target device, else host copies per run
                self._params = jax.tree.map(put, params)
                self._bufs = (jax.tree.map(put, bufs)
                              if bufs is not None else None)
            self._compiled = self._build_runner()

        self._inputs: Dict[str, Tensor] = {
            n: Tensor(n, self._device) for n in feed_names}
        self._feed_names = feed_names
        self._outputs: Dict[str, Tensor] = {}
        self._output_names: List[str] = []

    def _build_runner(self):
        exported = self._exported
        if self._kind == "serving":
            # the artifact is a live model (save_for_serving dir): the
            # runner is one jitted functional forward — quantized
            # Linears route to the fused dequant GEMM inside this
            # program exactly as they do in ServingEngine's tick
            from ..core.tensor import Tensor
            from ..nn.layer import functional_call
            model = exported

            def run_fn(args, params, bufs):
                logits = functional_call(model, params, (Tensor(args[0]),),
                                         buffers=bufs, training=False)
                return [logits]
        elif self._kind == "static":
            def run_fn(feeds, params):
                return exported.call(feeds, params)
        else:
            def run_fn(args, params, bufs):
                # raw key form — must match the aval jit.save exported
                # (typed keys don't serialize on jax<0.6)
                key = jax.random.PRNGKey(0)
                outs, _ = exported.call(params, bufs, key, *args)
                return outs
        # Two executables: the zero-copy path must NOT donate feeds (handles
        # keep referencing them across run() calls — the reference's
        # ZeroCopyRun contract allows re-running with the same bound inputs);
        # the convenience run(inputs) path re-binds feeds every call, so
        # donating them there is safe and is what enable_memory_optim buys.
        keep = jax.jit(run_fn)
        if self._ctx.donate_feeds:
            from ..observability.sanitizers import sanitize_donation
            donating = sanitize_donation(
                jax.jit(run_fn, donate_argnums=(0,)),
                donate_argnums=(0,), site="predictor.run")
        else:
            donating = keep
        return (keep, donating)

    # -- handles -----------------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_input_handle(self, name: str) -> Tensor:
        return self._inputs[name]

    get_input_tensor = get_input_handle

    def get_output_names(self) -> List[str]:
        if not self._output_names:
            n = self._fetch_count if self._fetch_count is not None else 1
            self._output_names = [f"fetch_{i}" for i in range(n)]
        return list(self._output_names)

    def get_output_handle(self, name: str) -> Tensor:
        if name not in self._outputs:
            self._outputs[name] = Tensor(name, self._device)
        return self._outputs[name]

    get_output_tensor = get_output_handle

    # -- execution ---------------------------------------------------------
    def run(self, inputs: Optional[List] = None):
        """ZeroCopyRun (ref ``analysis_predictor.h:182``). With ``inputs``
        given, behaves like the new paddle_infer convenience API: binds them
        positionally and returns numpy outputs."""
        if inputs is not None:
            for name, arr in zip(self._feed_names, inputs):
                self._inputs[name].copy_from_cpu(np.asarray(arr))
        feeds = []
        for n in self._feed_names:
            h = self._inputs[n]
            if h._value is None:
                raise RuntimeError(f"input '{n}' not set; copy_from_cpu first")
            feeds.append(h._value)
        runner = self._compiled[1 if inputs is not None else 0]
        donated = inputs is not None and self._ctx.donate_feeds
        if self._kind == "static":
            outs = runner(feeds, self._params)
        else:
            outs = runner(feeds, self._params, self._bufs)
        if donated:
            # feed buffers are gone; force a clear error (not a deleted-buffer
            # crash) if a later zero-copy run() reuses the stale handles
            for n in self._feed_names:
                self._inputs[n]._value = None
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        outs = jax.tree.leaves(outs)
        self._output_names = [f"fetch_{i}" for i in range(len(outs))]
        for i, v in enumerate(outs):
            self.get_output_handle(self._output_names[i])._value = v
        if inputs is not None:
            return [np.asarray(v) for v in outs]
        return True

    def clone(self) -> "Predictor":
        shared = (self._kind, self._exported, self._params, self._bufs,
                  list(self._feed_names), self._fetch_count, self._compiled)
        return Predictor(self._config, _shared=shared)

    def clear_intermediate_tensor(self):
        for h in self._outputs.values():
            h._value = None


def create_predictor(config: Config) -> Predictor:
    """Ref ``CreatePaddlePredictor`` (``api/analysis_predictor.cc``)."""
    return Predictor(config)


class PredictorPool:
    """Ref ``PredictorPool`` (``api/paddle_inference_api.h``): one main
    predictor + size-1 clones sharing weights/executable."""

    def __init__(self, config: Config, size: int = 1):
        main = create_predictor(config)
        self._preds = [main] + [main.clone() for _ in range(max(0, size - 1))]

    def retrieve(self, idx: int) -> Predictor:
        return self._preds[idx]
