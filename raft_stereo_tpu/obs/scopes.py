"""Which model component a device operation belongs to.

A TPU trace names an operation by its optimized-HLO line WITHOUT metadata
(`%fusion.2565 = bf16[...] fusion(...)`), so no `jax.named_scope` reaches a
trace reader directly. The same optimized module, printed by the program
(`compiled.as_text()`), carries every instruction's `op_name` — the
name-stack path flax's module scopes and this repo's `jax.named_scope`s
wrote at trace time. This module is the join:

- `instruction_scopes(hlo_text)`: instruction name -> (op_name, opcode) for
  every instruction of every computation of a module's text
  (`parse_instruction` reads one line, a trace event's name included);
- `component(op_name, opcode)`: ONE table from a path to (component, phase),
  the components being PERF.md's layer map letter for letter;
- `register(label, hlo_text_fn)` / `registered()`: the programs this process
  ran, by label. `hlo_text_fn` is called only when somebody asks, once, and
  must hold no device buffer (abstract shapes and the jitted callable).

Scopes the program writes (beside flax's module names `cnet`, `fnet`,
`context_zqr_conv*`, `update_block/{encoder,gru08,gru16,gru32,flow_head}`,
`mask_head`): `corr_build` (ops/corr.py, ops/corr_pallas.py), `corr_lookup`
(every lookup implementation and its VJP), `interp_pool` (models/update.py),
`upsample` (utils/geometry.py), `sequence_loss` (train/loss.py),
`grad_clip` and `optimizer` (train/optimizer.py, train/trainer.py). The
`sdar-moe` family (models/sdar_moe.py) writes flax's module names `embed`,
`layers/{input_norm,attention,post_attention_norm,router,experts}`, `norm`,
`lm_head`, and the scopes `block_attention` (ops/block_attention.py) and
`qk_norm_rope` (ops/qk_norm_rope.py; its backward is the Pallas call
`qk_norm_rope_bwd` inside the scope; both under `attention`),
`grouped_matmul` and `swiglu_rows` (ops/grouped_matmul.py; the
activation's backward is the Pallas call `swiglu_rows_bwd` inside the scope
`swiglu_rows`), `gather_rows` and `scatter_add_rows` (ops/tile_rows.py; all
four under `experts`) and `block_diffusion_loss`; the two norms before a
sublayer count with it. The `granite-hybrid` family
(models/granite_hybrid.py) writes flax's module names `embed`,
`layers_<i>/{ssm_norm,mixer,input_norm,attention,mlp_norm,mlp}`, `norm`, and
inside `mixer` the scopes `ssm_proj` (both projections; the layer's norm and
residual count with it), `ssm_conv` (the causal convolution, `silu`, the
split and `dt`'s softplus), `ssm_scan` (ops/ssd_scan.py: the running sums,
`C B^T`, and the kernels `ssd_chunk` / `ssd_chunk_bwd`, which carry the
state over the chunks) and `ssm_gate_norm`; `block_attention` under `attention`; `lm_head`
and `next_token_loss`. The `laguna-moe` family (models/laguna.py) writes
flax's module names `embed`, `layers_<i>/{attention_full,attention_window}`
(a layer's attention by its kind, its norm, gate and residual inside; the
scopes `qk_norm_rope` and `block_attention` or `window_attention` under
them), `layers_<i>/{mlp_norm,mlp}` or `layers_<i>/{post_attention_norm,
router,experts,shared_expert}`, `norm`, `lm_head` and `next_token_loss`.

Pure: jax is touched only by `scoped` (at trace time) and `abstract`; nothing
is lowered or parsed until `registered()` is called.
"""

from __future__ import annotations

import functools
import logging
import re
import threading
from typing import Callable, Dict, Tuple

logger = logging.getLogger(__name__)

Scopes = Dict[str, Tuple[str, str]]  # instruction name -> (op_name, opcode)

COMPONENTS = (
    "encoder", "corr_build", "lookup", "motion_encoder", "gru08", "gru16",
    "gru32", "flow_head", "interp_pool", "mask_head", "upsample",
    # the `sdar-moe` family's
    "embed", "attention", "router", "experts", "lm_head",
    # the `granite-hybrid` family's, beside `embed`, `attention`, `lm_head`
    "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "mlp",
    # the `laguna-moe` family's, beside `embed`, `router`, `experts`, `mlp`, `lm_head`
    "attention_full", "attention_window", "shared_expert",
    "loss", "optimizer", "collective", "other", "unscoped",
)
PHASES = ("forward", "backward", "recompute")

# The table. A row matches when its pattern ends the path at some `/`
# boundary; the innermost (rightmost) match wins.
_ROWS = tuple(
    (re.compile(r"(?:^|/)(?:" + pattern + r")$"), name)
    for pattern, name in (
        (r"cnet|fnet|context_zqr_conv\d+|conv2_res|conv2_out", "encoder"),
        (r"corr_build", "corr_build"),
        (r"corr_lookup", "lookup"),
        (r"update_block/encoder", "motion_encoder"),
        (r"gru08", "gru08"),
        (r"gru16", "gru16"),
        (r"gru32", "gru32"),
        (r"flow_head", "flow_head"),
        (r"interp_pool", "interp_pool"),
        (r"mask_head", "mask_head"),
        (r"upsample", "upsample"),
        (r"embed", "embed"),
        (r"attention|input_norm", "attention"),
        (r"attention_full", "attention_full"),
        (r"attention_window", "attention_window"),
        (r"shared_expert", "shared_expert"),
        (r"router|post_attention_norm", "router"),
        (r"experts", "experts"),
        (r"ssm_proj|ssm_norm", "ssm_proj"),
        (r"ssm_conv", "ssm_conv"),
        (r"ssm_scan", "ssm_scan"),
        (r"ssm_gate_norm|gate_norm", "ssm_gate_norm"),
        (r"mlp|mlp_norm", "mlp"),
        (r"lm_head(?:\.\w+)?|norm", "lm_head"),  # flax names a method other than __call__ `module.method`
        (r"sequence_loss|block_diffusion_loss|next_token_loss", "loss"),
        (r"grad_clip|optimizer", "optimizer"),
    )
)
_COLLECTIVE = re.compile(
    r"^(?:all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)(?:-start|-done)?$"
)
_REMAT_MARK = "rematted_computation"
_WRAPPED = re.compile(r"(?:\w+\()+([^/()]*)\)+")


def component(op_name: str, opcode: str = "") -> Tuple[str, str]:
    """(component, phase) of one instruction. Collectives go by opcode; an
    instruction without an `op_name` is `unscoped`; one whose path fits no
    row is `other`."""
    path = op_name.split(";", 1)[0]  # merged instructions list several paths
    if _REMAT_MARK in path:
        phase = "recompute"
    elif "transpose(" in path:
        phase = "backward"
    else:
        phase = "forward"
    # A transformation wraps the first scope it meets: `jvp(sequence_loss)`,
    # `transpose(jvp(RAFTStereo))`. The table reads the bare names.
    path = _WRAPPED.sub(r"\1", path)
    if _COLLECTIVE.match(opcode):
        return "collective", phase
    if not path:
        return "unscoped", phase
    end = len(path)
    while end > 0:
        prefix = path[:end]
        for pattern, name in _ROWS:
            if pattern.search(prefix):
                return name, phase
        end = path.rfind("/", 0, end)
    return "other", phase


def scoped(name: str):
    """Decorator: trace the function under `jax.named_scope(name)`. (A fresh
    scope per call: one `jax.named_scope` object used as a decorator keeps its
    saved stack in itself and is not safe under two tracing threads.)"""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def abstract(tree):
    """`tree` as `jax.ShapeDtypeStruct` leaves that lower exactly as the
    arrays themselves would: a committed array keeps its sharding, an
    uncommitted one (or a numpy array) leaves it to the jit — so a printer
    that lowers against the result asks the compile cache for the program
    that ran, and pins no buffer."""
    import jax

    def leaf(x):
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree.map(leaf, tree)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def _after_shape(line: str, start: int) -> int:
    """Index just past the result type that starts at `start`: a tuple type
    is parenthesised and holds spaces, an array type ends at the next space."""
    if line[start] != "(":
        end = line.find(" ", start)
        return len(line) if end < 0 else end
    depth = 0
    for i in range(start, len(line)):
        depth += (line[i] == "(") - (line[i] == ")")
        if depth == 0:
            return i + 1
    return len(line)


def parse_instruction(line: str):
    """(name without `%`, opcode, op_name or "") of one HLO instruction line
    — a line of a module's text, or a trace event's name, which is the same
    line without its metadata; None for any other line."""
    found = _INSTRUCTION.match(line)
    if not found:
        return None
    opcode = _OPCODE.match(line, _after_shape(line, found.end()))
    if not opcode:
        return None
    op_name = _OP_NAME.search(line, opcode.end())
    return found.group(1), opcode.group(1), op_name.group(1) if op_name else ""


def instruction_scopes(hlo_text: str) -> Scopes:
    """instruction name (no `%`) -> (op_name or "", opcode), over every
    computation of an optimized module's text."""
    out: Scopes = {}
    for line in hlo_text.splitlines():
        parsed = parse_instruction(line)
        if parsed:
            name, opcode, op_name = parsed
            out[name] = (op_name, opcode)
    return out


# -- registry -----------------------------------------------------------------

_lock = threading.Lock()
_pending: Dict[str, Callable[[], str]] = {}
_resolved: Dict[str, Scopes] = {}


def register(label: str, hlo_text_fn: Callable[[], str]) -> None:
    """Remember how to print the optimized module that runs under `label`.
    Costs a dict store; `hlo_text_fn` runs only inside `registered()`. It
    must not keep a device buffer alive: close over abstract shapes and the
    jitted callable, never over parameters or a compiled executable."""
    with _lock:
        _pending[label] = hlo_text_fn
        _resolved.pop(label, None)


def registered() -> Dict[str, Scopes]:
    """label -> instruction_scopes of every registered program. Each
    `hlo_text_fn` is called once, here, and then dropped; one that raises is
    logged and left out."""
    with _lock:
        todo = list(_pending.items())
    for label, fn in todo:
        try:
            scopes = instruction_scopes(fn())
        except Exception:  # noqa: BLE001 - a reader must not take the run down
            logger.warning("could not print the program registered as %r", label, exc_info=True)
            scopes = None
        with _lock:
            if _pending.get(label) is fn:
                del _pending[label]
                if scopes is not None:
                    _resolved[label] = scopes
    with _lock:
        return dict(_resolved)


def clear() -> None:
    """Forget every registration (tests)."""
    with _lock:
        _pending.clear()
        _resolved.clear()
