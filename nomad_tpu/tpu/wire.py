"""The wire layout of one batched dispatch (ROADMAP D2, begun).

Each array a dispatch sends or fetches by itself costs host time whatever
its size (65 us up, 400 us down on the v5e), and the scan takes 26 static
+ 12 carry + 10 xs arrays and each eval's step count, and returns five. So
the 49 fields travel as ONE flat buffer per dtype, and the five results
come back as ONE array. ``FIELDS`` is the single table both sides read:
``pack`` (host, numpy) writes each eval's arrays straight into their
padded slots of the preallocated buffers, and ``unpack`` (inside the
jitted program, or numpy in the tests) slices the buffers back into the
``(static_b, carry_b, xs_b, p_real)`` the batched scan takes. Packing
moves bits and computes nothing: every fill, remap and cast below is
``batcher.pad_encoded``'s, which stays as the reference the tests hold
this module to (and as the mesh path's own padder).

Buffers are field-major: a group's buffer holds, field after field, the
contiguous block ``[b_pad, *padded_shape]`` of that field (node-minor:
``_on_the_wire``), each block starting on an ``ALIGN``-element boundary
so the in-program slices start on a tile of the device's 1-D layout
(16.8 against 15.0 ms a 16-wide dispatch without it, PERF.md section 6).
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .intscore import E27_ONE as _E27_NEUTRAL

ALIGN = 1024

#: ``Field.cast`` of the fields that ride the eval's own dtype (int32 in
#: the exact integer spec, float32/float64 in the throughput modes)
MODE = "mode"

N_STATIC, N_CARRY, N_XS = 26, 12, 10


class Field(NamedTuple):
    """One array of the scan's inputs. ``axes`` names, per axis, the
    dispatch dim it pads to (a key of the batcher's dims without its
    ``_pad``), ``"="`` for an axis that keeps its own size, or a dim with
    a trailing ``"?"``: padded only when the array's LEADING axis is
    non-empty (absent tables ride zero-height and stay so). ``fill`` is
    the pad value, or the name of one computed per eval (``_fill``)."""

    part: str
    name: str
    cast: object
    axes: Tuple[str, ...]
    fill: object = 0


def _fields() -> Tuple[Field, ...]:
    s, c, x, b = "static", "carry", "xs", "bound"
    i32 = np.int32
    return (
        Field(s, "totals", MODE, ("n", "d")),
        # int-mode evals fold reserved into totals and pass it zero-height
        Field(s, "reserved", MODE, ("n?", "d")),
        Field(s, "asks", MODE, ("g", "d")),
        # packed feature plane: padded rows and nodes are 0 = infeasible
        Field(s, "feat_packed", None, ("g", "n")),
        Field(s, "aff_score", MODE, ("aff", "n")),
        Field(s, "desired_counts", None, ("g",), 1),
        Field(s, "dh_job", None, ("g",), False),
        Field(s, "dh_tg", None, ("g",), False),
        Field(s, "limits", None, ("g",)),
        # the eval's invalid vocab bucket (v-1) is remapped onto the
        # batch's (v_pad-1); new cells are invalid too
        Field(s, "spread_vids", i32, ("g", "s", "n"), "v_pad-1"),
        Field(s, "spread_desired", MODE, ("g", "s", "v"), -1.0),
        Field(s, "spread_weights", MODE, ("g", "s")),
        Field(s, "spread_has_targets", None, ("g", "s"), False),
        Field(s, "spread_active", None, ("g", "s"), False),
        Field(s, "sum_spread_weights", MODE, ("g",)),
        Field(s, "n_real", i32, ()),
        # Q27 ask factors (int mode; zero-sized in float batches)
        Field(s, "e_ask", None, ("g?", "n?", "="), _E27_NEUTRAL),
        # distinct_property: the eval's MISSING bucket onto the batch's
        Field(s, "dp_vids", None, ("dpd", "n"), "dpv_pad-1"),
        Field(s, "dp_limit", None, ("dpd",), 1),
        Field(s, "dp_applies", None, ("g", "dpd"), False),
        # preemption candidate axis: zero-width unless the batch preempts
        Field(s, "pre_res", None, ("n", "prec", "=")),
        Field(s, "pre_prio", None, ("n", "prec")),
        Field(s, "pre_elig", None, ("n", "prec"), False),
        Field(s, "pre_mp", None, ("n", "prec")),
        Field(s, "pre_gid", None, ("n", "prec")),
        Field(s, "pre_evf", None, ("n", "prec", "="), _E27_NEUTRAL),

        Field(c, "used0", MODE, ("n", "d")),
        Field(c, "tg_counts0", None, ("g", "n")),
        Field(c, "job_counts0", None, ("n",)),
        Field(c, "spread_counts0", MODE, ("g", "s", "v")),
        Field(c, "spread_entry0", None, ("g", "s", "v"), False),
        Field(c, "offset0", i32, ()),
        # a TG slot that only a co-batched eval of more groups forces is
        # pre-failed (no step of this eval points at it)
        Field(c, "failed0", None, ("g",), True),
        Field(c, "e_base0", None, ("n?", "="), _E27_NEUTRAL),
        Field(c, "dp_counts0", None, ("dpd", "dpv")),
        Field(c, "pre_alive0", None, ("n", "prec"), False),
        # zero-height without candidate tables; a preempting batch needs
        # full rows (zeros are inert: widened evals are never eligible)
        Field(c, "pre_remaining0", None, ("n_if_prec", "=")),
        Field(c, "pre_counts0", None, ("pregp",)),

        # padded steps are masked by index (p_real) whatever they hold
        Field(x, "tg_idx", None, ("p",)),
        Field(x, "penalty_idx", None, ("p", "k"), -1),
        Field(x, "evict_node", None, ("p",), -1),
        Field(x, "evict_res", MODE, ("p", "evd")),
        Field(x, "evict_tg", None, ("p",), -1),
        Field(x, "limit_p", None, ("p",)),
        Field(x, "sum_sw_p", MODE, ("p",), 1.0),
        Field(x, "ev_factor", None, ("p", "fac"), _E27_NEUTRAL),
        Field(x, "rev_factor", None, ("p", "fac"), _E27_NEUTRAL),
        Field(x, "forced_node", None, ("p", "fnd"), -1),

        # the eval's own step count (enc.p): the device runs the wave's
        # longest eval and masks each eval's steps from its count on
        Field(b, "p_real", i32, ()),
    )


FIELDS = _fields()
assert [f.part for f in FIELDS] == (
    ["static"] * N_STATIC + ["carry"] * N_CARRY + ["xs"] * N_XS + ["bound"])
_SPREAD_VIDS = next(i for i, f in enumerate(FIELDS) if f.name == "spread_vids")
_DP_VIDS = next(i for i, f in enumerate(FIELDS) if f.name == "dp_vids")
_DP_COUNTS0 = next(i for i, f in enumerate(FIELDS) if f.name == "dp_counts0")


def eval_arrays(enc) -> tuple:
    """The eval's arrays in ``FIELDS`` order."""
    arrays = (tuple(enc.static) + tuple(enc.carry) + tuple(enc.xs)
              + (np.int32(enc.p),))
    if (len(enc.static), len(enc.carry), len(enc.xs)) != (
            N_STATIC, N_CARRY, N_XS):
        raise ValueError(
            f"encoded eval carries {len(enc.static)}+{len(enc.carry)}+"
            f"{len(enc.xs)} arrays, the wire layout {N_STATIC}+{N_CARRY}+"
            f"{N_XS}")
    return arrays


def _padded_shape(field: Field, own: tuple, dims: Dict[str, int]) -> tuple:
    if len(own) != len(field.axes):
        raise ValueError(f"{field.name}: shape {own} against axes {field.axes}")
    present = bool(own) and own[0] > 0
    shape = []
    for axis, size in zip(field.axes, own):
        if axis == "=":
            shape.append(size)
        elif axis == "n_if_prec":
            shape.append(dims["n_pad"] if present or dims["prec_pad"] else 0)
        elif axis.endswith("?"):
            shape.append(dims[axis[:-1] + "_pad"] if present else size)
        else:
            shape.append(dims[axis + "_pad"])
    return tuple(shape)


def shape_key(enc, dims: Dict[str, int], dtype) -> tuple:
    """``((padded shape, dtype name), ...)`` of one eval's 49 fields in a
    dispatch of these dims: what the batcher keys its compiled shapes and
    its layouts on. Conditional axes are read off this eval."""
    mode = np.dtype(dtype)
    key = []
    for field, arr in zip(FIELDS, eval_arrays(enc)):
        own = np.shape(arr)
        dt = (mode if field.cast is MODE
              else np.dtype(field.cast) if field.cast is not None
              else np.asarray(arr).dtype)
        key.append((_padded_shape(field, own, dims), str(dt)))
    return tuple(key)


class Slot(NamedTuple):
    """Where one field's ``[b_pad, *shape]`` block sits: elements
    ``[offset, offset + b_pad * size)`` of buffer ``group``. ``node_axis``
    is the axis of ``shape`` that runs over nodes when it is not the last
    one (else None): on the wire such a block is stored with that axis
    moved last (``_on_the_wire``)."""

    group: int
    offset: int
    size: int
    shape: tuple
    dtype: np.dtype
    node_axis: Optional[int]


def _on_the_wire(flat, slot: Slot, b_pad: int, xp):
    """A field's flat block as its ``[b_pad, *shape]`` array. A plane
    whose node axis is not its last ([N, D] totals, [G, N, 2] factors)
    travels node-minor: the device keeps such planes with the nodes along
    the lanes, so this moveaxis costs it nothing, where a flat block read
    as [.., N, 4] is a lane-sparse relayout (3.2 ms of a 16-wide dispatch
    and 0.2 ms of a lone one on the v5e, PERF.md section 6)."""
    if slot.node_axis is None:
        return flat.reshape((b_pad,) + slot.shape)
    shape = list(slot.shape)
    shape.append(shape.pop(slot.node_axis))
    return xp.moveaxis(flat.reshape([b_pad] + shape), -1, 1 + slot.node_axis)


class WireLayout:
    """Offsets of the 49 fields in the per-dtype buffers of one padded
    shape at one batch bucket. Computed once per (shape key, b_pad) and
    cached by the batcher; hashable, so the jitted program takes it as a
    static argument and compiles once per layout. ``bool`` fields ride
    the ``uint8`` buffer (0/1) and are read back with ``!= 0``."""

    def __init__(self, key: tuple, b_pad: int, dims: Dict[str, int]) -> None:
        self.key = key
        self.b_pad = int(b_pad)
        self.n_pad = int(dims["n_pad"])
        self.p_pad = int(dims["p_pad"])
        self.v_pad = int(dims["v_pad"])
        self.dpv_pad = int(dims["dpv_pad"])
        self.prec_pad = int(dims["prec_pad"])
        mode = np.dtype(key[0][1])
        # what the scan returns: chosen, scores (int64 score60s in the
        # integer spec, the eval's float dtype in the throughput modes),
        # pulls, skipped, one eviction rank per preemption candidate
        # (an empty int32 column where the batch has none) and the
        # near-tie rival; pack_outputs
        # holds the program to it. They come back in one int32 array, or
        # a float64 one for a float64 batch (pack_outputs says why).
        self.out_dtypes = tuple(np.dtype(d) for d in (
            np.int32, np.int64 if mode.kind == "i" else mode, np.int32,
            np.bool_, np.int64 if self.prec_pad else np.int32, np.int32))
        self.carrier = np.dtype(
            np.float64 if mode == np.float64 else np.int32)
        carriers: List[np.dtype] = []
        ends: List[int] = []
        slots = []
        for field, (shape, dtype_name) in zip(FIELDS, key):
            dtype = np.dtype(dtype_name)
            carrier = np.dtype(np.uint8) if dtype == np.bool_ else dtype
            size = int(np.prod(shape, dtype=np.int64))
            if size == 0:
                slots.append(Slot(-1, 0, 0, shape, dtype, None))
                continue
            node_axis = next((i for i, axis in enumerate(field.axes[:-1])
                              if axis in ("n", "n?", "n_if_prec")), None)
            if carrier not in carriers:
                carriers.append(carrier)
                ends.append(0)
            gi = carriers.index(carrier)
            slots.append(Slot(gi, ends[gi], size, shape, dtype, node_axis))
            used = ends[gi] + self.b_pad * size
            ends[gi] = -(-used // ALIGN) * ALIGN
        self.slots: Tuple[Slot, ...] = tuple(slots)
        self.groups: Tuple[Tuple[np.dtype, int], ...] = tuple(zip(carriers, ends))
        self._hash = hash((key, self.b_pad))

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return self is other or (
            isinstance(other, WireLayout) and self.b_pad == other.b_pad
            and self.key == other.key)


class WireBuffers:
    """One layout's host buffers and a ``[b_pad, *shape]`` view of each
    field in them. Owned by one thread at a time: the dispatcher reuses
    its set across dispatches because it waits for a dispatch's output,
    and so for the end of its uploads, before it packs the next."""

    def __init__(self, layout: WireLayout) -> None:
        self.layout = layout
        self.arrays = tuple(np.zeros(n, dt) for dt, n in layout.groups)
        self.views: Tuple[Optional[np.ndarray], ...] = tuple(
            None if slot.size == 0 else _on_the_wire(
                self.arrays[slot.group][
                    slot.offset:slot.offset + layout.b_pad * slot.size
                ].view(slot.dtype), slot, layout.b_pad, np)
            for slot in layout.slots)


def _fill(field: Field, layout: WireLayout):
    fill = field.fill
    if not isinstance(fill, str):
        return fill
    return (layout.v_pad if fill == "v_pad-1" else layout.dpv_pad) - 1


def pack(bufs: WireBuffers, encs: Sequence) -> None:
    """Write ``encs`` into slots ``0..len(encs)-1`` of every field, padded
    as ``pad_encoded`` pads them, then fill the slots up to ``b_pad`` with
    inert copies of slot 0 (their results are discarded). Raises
    ``ValueError`` for an eval that does not fit the layout."""
    layout = bufs.layout
    b = len(encs)
    if not 0 < b <= layout.b_pad:
        raise ValueError(f"{b} evals into a layout of {layout.b_pad}")
    for bi, enc in enumerate(encs):
        arrays = eval_arrays(enc)
        dp_missing = np.shape(arrays[_DP_COUNTS0])[1] - 1
        for i, (field, view, src) in enumerate(zip(FIELDS, bufs.views, arrays)):
            if view is None:
                continue
            src = np.asarray(src)
            shape = view.shape[1:]
            own = (bi,)  # the eval's own extent inside its padded slot
            if src.shape != shape:
                if src.ndim != len(shape) and src.size:
                    raise ValueError(
                        f"{field.name}: shape {src.shape} into {shape}")
                fill = _fill(field, layout)
                if src.size == 0:
                    view[bi] = fill
                    continue
                # the margins only: along each axis, what lies past the
                # eval's own extent (the slabs overlap in the corners)
                for ax, (k, padded) in enumerate(zip(src.shape, shape)):
                    if k < padded:
                        view[(bi,) + (slice(None),) * ax
                             + (slice(k, None),)] = fill
                own += tuple(slice(0, k) for k in src.shape)
            view[own] = src
            if i == _SPREAD_VIDS:
                np.putmask(view[own], view[own] >= enc.v - 1,
                           layout.v_pad - 1)
            elif i == _DP_VIDS:
                np.putmask(view[own], view[own] >= dp_missing,
                           layout.dpv_pad - 1)
    if b < layout.b_pad:
        for view in bufs.views:
            if view is not None:
                view[b:] = view[0]


def unpack(layout: WireLayout, arrays: Sequence, xp) -> tuple:
    """The ``(static_b, carry_b, xs_b, p_real)`` the batched scan takes, as
    slices of the group buffers. ``xp`` is ``jax.numpy`` inside the program
    and ``numpy`` on the host: the same code reads the table either way."""
    out = []
    for slot in layout.slots:
        if slot.size == 0:
            out.append(xp.zeros((layout.b_pad,) + slot.shape, slot.dtype))
            continue
        flat = arrays[slot.group][
            slot.offset:slot.offset + layout.b_pad * slot.size]
        if slot.dtype == np.bool_:
            flat = flat != 0
        out.append(_on_the_wire(flat, slot, layout.b_pad, xp))
    xs_end = N_STATIC + N_CARRY + N_XS
    return (tuple(out[:N_STATIC]), tuple(out[N_STATIC:N_STATIC + N_CARRY]),
            tuple(out[N_STATIC + N_CARRY:xs_end]), out[xs_end])


def pack_outputs(layout: WireLayout, chosen, scores, pulls, skipped, evict,
                 rival):
    """Inside the program: the scan's six outputs ``[b, p, ...]`` as ONE
    array ``[b, p * lanes]``, segment after segment, in the dtypes the
    layout declares (checked here, while tracing). Every int32 lane is a
    bitcast (an int64 is its two halves), so what ``split_outputs`` hands
    back is bit for bit what the scan produced; ``skipped`` rides as 0/1.
    A float64 batch rides a float64 array instead — the TPU's compiler
    has no bitcast of a float64 — where the scores are untouched and the
    integers, all far below 2**53, are carried by value, exactly."""
    import jax.lax as lax
    import jax.numpy as jnp

    outs = (chosen, scores, pulls, skipped, evict, rival)
    if tuple(np.dtype(o.dtype) for o in outs) != layout.out_dtypes:
        raise TypeError(
            f"the scan returns {[str(o.dtype) for o in outs]}, the wire "
            f"layout declares {[str(d) for d in layout.out_dtypes]}")
    b = chosen.shape[0]

    def lanes(a):
        if layout.carrier == np.float64 or a.dtype == jnp.bool_:
            a = a.astype(layout.carrier)
        else:
            a = lax.bitcast_convert_type(a, jnp.int32)
        return a.reshape(b, -1)

    return jnp.concatenate([lanes(o) for o in outs], axis=1)


def split_outputs(layout: WireLayout, host: np.ndarray):
    """The one host array back as ``(chosen, scores, pulls, skipped,
    evict, rival)``, each ``[b_pad, p_pad, ...]`` in its declared dtype: views of
    ``host`` where a lane is a bitcast, copies where it rode by value."""
    b, p = host.shape[0], layout.p_pad
    at = 0

    def take(dtype, width=None):
        nonlocal at
        by_value = layout.carrier == np.float64 or dtype == np.bool_
        n = p * (width or 1) * (1 if by_value else dtype.itemsize // 4)
        seg = host[:, at:at + n]
        at += n
        seg = (seg != 0) if dtype == np.bool_ else (
            seg.astype(dtype) if by_value else seg.view(dtype))
        return seg if width is None else seg.reshape(b, p, width)

    chosen, scores, pulls, skipped = (take(d) for d in layout.out_dtypes[:4])
    if layout.prec_pad == 0:
        evict = np.zeros((b, p, 0), layout.out_dtypes[4])
    else:
        evict = take(layout.out_dtypes[4], layout.prec_pad)
    return chosen, scores, pulls, skipped, evict, take(layout.out_dtypes[5])
