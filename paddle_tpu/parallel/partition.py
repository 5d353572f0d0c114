"""One partition-rule engine for train AND serve.

Regex partition rules over a parameter pytree — the TPU-idiomatic
pattern for mapping an arbitrary model's params onto a mesh without
hand-annotating every leaf: each rule is ``(regex, PartitionSpec)``,
matched with `re.search` against the '/'-joined tree path of the leaf
('blocks/3/qkv_w', 'w_fc2', ...).  First match wins; scalars and
size-1 leaves are always replicated (sharding a scalar buys nothing
and trips GSPMD's divisibility checks).

`models.gpt_spmd.param_specs` (the train-side conventions: column-split
qkv/fc1, row-split out/fc2 with psum at row outputs, replicated norms)
routes through `match_partition_rules` with `gpt_train_rules`;
`inference.serving.DecodeEngine` shards its per-block serving pytree
with `gpt_serving_rules` — the SAME split geometry, minus the pp/vocab
axes that only exist under training's stacked-layer layout.

Also here because both the costmodel and the multichip tests need it:
`hlo_collectives`, a text parser that reads all-reduce/all-gather/
reduce-scatter/collective-permute shapes (and their byte volumes) out
of optimized HLO — the roofline's interconnect term and the test
suite's "the sharded program really communicates where the math says
it must" assertion share one implementation.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "tree_path_names",
    "match_partition_rules",
    "make_shard_and_gather_fns",
    "gpt_train_rules",
    "gpt_serving_rules",
    "kv_pages_spec",
    "parse_mesh_spec",
    "build_mesh",
    "hlo_collectives",
    "collective_bytes",
]


# ---------------------------------------------------------------------------
# tree paths + rule matching
# ---------------------------------------------------------------------------
def _key_name(k) -> str:
    tu = jax.tree_util
    if isinstance(k, tu.DictKey):
        return str(k.key)
    if isinstance(k, tu.SequenceKey):
        return str(k.idx)
    if isinstance(k, tu.GetAttrKey):
        return str(k.name)
    if isinstance(k, tu.FlattenedIndexKey):
        return str(k.key)
    return str(k)


def tree_path_names(tree, sep: str = "/") -> List[str]:
    """'/'-joined key path of every leaf, in tree_leaves order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [sep.join(_key_name(k) for k in path) for path, _ in flat]


def match_partition_rules(rules: Sequence[Tuple[str, P]], params):
    """Map a pytree of arrays to a pytree of PartitionSpecs.

    Scalars and size-1 leaves replicate unconditionally; everything
    else takes the spec of the FIRST rule whose regex `re.search`-es
    its '/'-joined path.  A leaf no rule covers raises — a silent
    replicate-by-default would hide a typo'd rule until the profile
    shows a replicated weight eating N× HBM.  (End a rule table with
    ``(".*", P())`` when replicate-by-default is the intent.)
    """
    def get_spec(path_keys, leaf):
        name = "/".join(_key_name(k) for k in path_keys)
        if np.ndim(leaf) == 0 or int(np.prod(np.shape(leaf))) == 1:
            return P()
        for rule, spec in rules:
            if re.search(rule, name):
                return spec
        raise ValueError(f"Partition rule not found for param: {name}")

    return jax.tree_util.tree_map_with_path(get_spec, params)


def make_shard_and_gather_fns(partition_specs, mesh: Mesh):
    """Per-leaf (shard, gather) callables from a spec pytree.

    shard: `jax.device_put` onto the leaf's NamedSharding (committed
    placement — GSPMD propagates from committed inputs, so jitted fns
    need no in_shardings).  gather: device→host `np.asarray` of the
    global value (works on any fully-addressable sharded array).
    """
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), partition_specs,
        is_leaf=lambda x: isinstance(x, P))

    def make_shard(s):
        return lambda x: jax.device_put(x, s)

    def make_gather(s):
        return lambda x: np.asarray(jax.device_get(x))

    shard_fns = jax.tree_util.tree_map(make_shard, shardings)
    gather_fns = jax.tree_util.tree_map(make_gather, shardings)
    return shard_fns, gather_fns


# ---------------------------------------------------------------------------
# GPT rule tables
# ---------------------------------------------------------------------------
def gpt_train_rules() -> List[Tuple[str, P]]:
    """Rules reproducing `gpt_spmd.param_specs` exactly: stacked-block
    params lead with the pp axis, qkv/fc1 column-split over mp (the
    packed qkv axis is head-major so contiguous mp shards hold whole
    heads), out/fc2 row-split (psum at the output), vocab-parallel
    embedding and lm_head."""
    return [
        (r"^wte$", P("mp", None)),
        (r"^wpe$", P()),
        (r"^(ln1|ln2)_(w|b)$", P("pp", None)),
        (r"^w_qkv$", P("pp", None, "mp")),
        (r"^b_qkv$", P("pp", "mp")),
        (r"^w_out$", P("pp", "mp", None)),
        (r"^w_fc1$", P("pp", None, "mp")),
        (r"^b_fc1$", P("pp", "mp")),
        (r"^w_fc2$", P("pp", "mp", None)),
        (r"^(b_out|b_fc2)$", P("pp", None)),
        (r"^lnf_(w|b)$", P()),
        (r"^lm_head$", P(None, "mp")),
    ]


def gpt_serving_rules() -> List[Tuple[str, P]]:
    """Rules for the serving params pytree (`_extract_gpt_params`:
    top-level wte/wpe/lnf/head + per-block 2-D weights, no stacked L
    dim).  Same tensor-parallel geometry as training over the single
    'mp' axis: qkv/fc1 column-split, out/fc2 row-split; biases of
    column-split matmuls shard with their columns; biases of row-split
    matmuls replicate (they add AFTER the cross-chip reduction, once).
    Embeddings, norms and the LM head replicate — decode is
    latency-bound on the per-block matmuls, and a replicated head
    keeps the greedy argmax bit-identical to one chip.  Catch-all
    replicates: serving has no vocab/pp axes to cover.

    serve_weights=int8 engines carry ``*_q``/``*_s`` pairs instead of
    the f32 originals; each pair shards on the SAME geometry — the
    int8 payload like its f32 twin, the per-out-channel scale like the
    column-split bias (it is a vector over the out axis), so the
    dequant multiply stays chip-local.  Row-split weights (out/fc2)
    leave the out axis unsharded, so their scales — and the replicated
    head's pair — fall through to the catch-all."""
    return [
        (r"qkv_w(_q)?$", P(None, "mp")),
        (r"(qkv_b|qkv_w_s)$", P("mp")),
        (r"out_w(_q)?$", P("mp", None)),
        (r"fc1_w(_q)?$", P(None, "mp")),
        (r"(fc1_b|fc1_w_s)$", P("mp")),
        (r"fc2_w(_q)?$", P("mp", None)),
        (r".*", P()),
    ]


def kv_pages_spec() -> P:
    """KV page pool [L, H, n_pages, page, W] (and an int8 pool's page
    scales [L, H, n_pages]: `ops.pallas.paged_attention.KVPool.sharded`):
    sharded on the head axis
    — each chip holds its head-slice of EVERY page, so page ids stay
    logical and the allocator/block tables stay host-global.  Trailing
    replicated axes are TRIMMED (``P(None, 'mp')``, not the 5-element
    form): jit reconstructs output shardings from HLO in the trimmed
    form, and the donated pool round-trips executable-output ->
    next-step-input — an untrimmed construction-time spec would differ
    from the step's own output spec and retrace the warm cache on the
    second step."""
    return P(None, "mp")


# ---------------------------------------------------------------------------
# mesh specs
# ---------------------------------------------------------------------------
def parse_mesh_spec(spec: str) -> List[Tuple[str, int]]:
    """'mp=2' / 'dp=2,mp=4' -> ordered [(axis, size), ...].  Raises on
    malformed axes or non-positive sizes; an empty string is an error
    here (callers treat empty as mesh-off BEFORE parsing)."""
    out: List[Tuple[str, int]] = []
    if not spec or not spec.strip():
        raise ValueError("empty mesh spec")
    for part in spec.split(","):
        m = re.fullmatch(r"\s*([A-Za-z_]\w*)\s*=\s*(\d+)\s*", part)
        if not m:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected 'axis=N[,axis=N...]'")
        name, n = m.group(1), int(m.group(2))
        if n <= 0:
            raise ValueError(f"bad mesh spec {spec!r}: {name}={n}")
        if any(name == a for a, _ in out):
            raise ValueError(f"bad mesh spec {spec!r}: duplicate axis {name}")
        out.append((name, n))
    return out


def build_mesh(spec: str, devices=None) -> Mesh:
    """Mesh from a spec string over the first prod(sizes) devices."""
    axes = parse_mesh_spec(spec)
    names = tuple(a for a, _ in axes)
    sizes = tuple(n for _, n in axes)
    need = int(np.prod(sizes))
    devs = list(jax.devices() if devices is None else devices)
    if need > len(devs):
        raise ValueError(
            f"mesh spec {spec!r} needs {need} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:need]).reshape(sizes), names)


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# one HLO instruction: `%name = <shapes> opcode(...)`.  Async pairs
# (`all-reduce-start`/`-done`) would double-count; only the non-`-done`
# half carries the transfer.
_COLL_RE = re.compile(
    r"=\s*(?P<shapes>.*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?P<suffix>-start)?\(")
_SHAPE_RE = re.compile(r"([a-z]\d*|pred|bf16)\[([0-9,]*)\]")


def _shape_bytes(shapes_text: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shapes_text):
        size = _DTYPE_BYTES.get(dt)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def hlo_collectives(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """Per-opcode {count, bytes} read from (optimized) HLO text.

    Bytes are the instruction's OUTPUT shape sizes — the volume the
    interconnect moves per call site, the quantity the roofline's ICI
    term divides by link bandwidth.  `-done` halves of async pairs are
    skipped (the `-start` already counted the transfer)."""
    out: Dict[str, Dict[str, Any]] = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        row = out.setdefault(op, {"count": 0, "bytes": 0.0})
        row["count"] += 1
        row["bytes"] += _shape_bytes(m.group("shapes"))
    return out


def collective_bytes(hlo_text: str) -> float:
    """Total bytes moved by collectives in one HLO program."""
    return float(sum(r["bytes"] for r in hlo_collectives(hlo_text).values()))
