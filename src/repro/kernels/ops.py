"""jit'd public wrappers over the Pallas kernels (the ``repro.nn`` backend).

Every wrapper takes a keyword-only ``interpret: bool = False``:

* ``False`` (the default) emits the real Mosaic TPU kernel, which needs a
  TPU; lowering it for any other platform fails.
* ``True`` runs the kernel body in Python (validation mode, any host).

Nothing picks interpret mode for the caller: host-only callers pass
``interpret=True`` themselves, and ``nn.set_backend("pallas_interpret")``
does so for every model call site. Signatures match the ``repro.nn``
call sites so ``nn.set_backend("pallas"/"pallas_interpret")`` swaps
implementations without touching model code.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import nms as _nms
from repro.kernels import norms as _norms
from repro.kernels import rope as _rope
from repro.kernels import softmax_xent as _xent
from repro.kernels import swiglu as _glu


def _autojit(kernel_fn, static):
    """Public wrapper factory: jit ``kernel_fn`` with ``static`` argnames
    (``interpret`` must be among them, so both modes cache separately)."""
    if "interpret" not in static:
        raise ValueError(f"{kernel_fn.__name__}: 'interpret' must be static")
    return jax.jit(kernel_fn, static_argnames=static)


rms_norm = _autojit(_norms.rms_norm,
                    static=("eps", "zero_centered", "block_rows",
                            "interpret"))
fused_add_rms_norm = _autojit(_norms.fused_add_rms_norm,
                              static=("eps", "zero_centered", "block_rows",
                                      "interpret"))
dequant_add_rms_norm = _autojit(_norms.dequant_add_rms_norm,
                                static=("eps", "zero_centered",
                                        "block_rows", "interpret"))
layer_norm = _autojit(_norms.layer_norm,
                      static=("eps", "block_rows", "interpret"))
fused_add_layer_norm = _autojit(_norms.fused_add_layer_norm,
                                static=("eps", "block_rows", "interpret"))
fused_rope = _autojit(_rope.rope,
                      static=("base", "fraction", "block_rows", "interpret"))
swiglu = _autojit(_glu.swiglu,
                  static=("block_rows", "block_cols", "interpret"))
geglu = _autojit(_glu.geglu,
                 static=("block_rows", "block_cols", "interpret"))
flash_attention = _autojit(_fa.flash_attention,
                           static=("causal", "window", "q_offset", "scale",
                                   "softcap", "block_q", "block_k",
                                   "interpret"))
softmax_xent = _autojit(_xent.softmax_xent,
                        static=("block_rows", "block_vocab", "interpret"))
nms = _autojit(_nms.nms,
               static=("iou_threshold", "score_threshold", "interpret"))


# ---------------------------------------------------------------------------
# Static kernel metadata (nglint NG005)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Static description of one public kernel entry point.

    ``block_defaults`` mirrors the kernel's block-shape keyword defaults;
    ``handles_remainder`` records how a partial last block is made legal:

    * ``"pad"``  — operands are padded up to a block multiple before the
      ``pallas_call`` (``_pad_rows`` in norms/rope, row+col pad in swiglu);
    * ``"clamp"`` — the block shape is clamped to the operand dim
      (``min(block, dim)`` in flash_attention / softmax_xent);
    * ``None``  — neither: block shapes MUST divide the operand dims, and
      nglint rule NG005 flags harvested shapes that don't.
    """

    name: str
    fn: Callable
    block_defaults: Mapping[str, int] = dataclasses.field(
        default_factory=dict)
    handles_remainder: Optional[str] = "pad"


def _spec(name: str, fn: Callable, remainder: Optional[str],
          **blocks: int) -> Tuple[str, KernelSpec]:
    return name, KernelSpec(name=name, fn=fn, block_defaults=dict(blocks),
                            handles_remainder=remainder)


#: every public kernel, keyed by the name ``FUSION_PATTERNS`` entries use
#: in their ``kernel=`` field — nglint NG005 cross-checks the two tables
KERNEL_SPECS: Dict[str, KernelSpec] = dict((
    _spec("rms_norm", rms_norm, "pad", block_rows=8),
    _spec("fused_add_rms_norm", fused_add_rms_norm, "pad", block_rows=8),
    _spec("dequant_add_rms_norm", dequant_add_rms_norm, "pad", block_rows=8),
    _spec("layer_norm", layer_norm, "pad", block_rows=8),
    _spec("fused_add_layer_norm", fused_add_layer_norm, "pad", block_rows=8),
    _spec("fused_rope", fused_rope, "pad", block_rows=8),
    _spec("swiglu", swiglu, "pad", block_rows=256, block_cols=512),
    _spec("geglu", geglu, "pad", block_rows=256, block_cols=512),
    _spec("flash_attention", flash_attention, "clamp",
          block_q=128, block_k=128),
    _spec("softmax_xent", softmax_xent, "clamp",
          block_rows=8, block_vocab=2048),
    _spec("nms", nms, "pad"),
))


# ---------------------------------------------------------------------------
# Template-generated attention variants (repro.kernels.attn_template)
# ---------------------------------------------------------------------------

def register_template_kernel(spec, raw_fn, static) -> Callable:
    """Auto-registration hook for :func:`attn_template.make_attention`.

    Wraps the generated raw entry point in :func:`_autojit` (so every
    variant takes the same static ``interpret`` flag) and records it in
    ``KERNEL_SPECS`` under ``attn_template:<name>`` at spec-instantiation
    time — nglint NG005 then vets the variant like any hand-written
    kernel, and flags instantiated specs missing from this table.
    """
    from repro.kernels import attn_template as _tmpl

    public = _autojit(raw_fn, static=static)
    key = _tmpl.kernel_key(spec)
    KERNEL_SPECS[key] = KernelSpec(
        name=key, fn=public,
        block_defaults={"block_q": spec.block_q, "block_k": spec.block_k},
        handles_remainder="clamp")
    return public


# instantiate (and thereby register) the built-in variants; attn_template
# defers this to the end of our import so the _autojit machinery exists
from repro.kernels import attn_template as _tmpl  # noqa: E402

for _s in _tmpl.BUILTIN_SPECS:
    if _s.name not in _tmpl._PUBLIC:
        _tmpl.make_attention(_s)
del _s

#: the decode-1q template variant — the fused decode kernel the engine
#: and the ``fused_attn_decode`` fusion pattern route through
attn_decode_template = _tmpl.get("decode")
#: its paged twin: K/V read in place from the block pool through the
#: block tables (``PagedEngine``'s decode on a kernel backend)
attn_decode_paged_template = _tmpl.get("paged_decode")
#: the full/cross variant (vision encoder, detector query refinement)
attn_full_template = _tmpl.get("full")
