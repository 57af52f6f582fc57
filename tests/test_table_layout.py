"""The visited table keeps ONE layout through the whole run program (PR 38).

``ops/buckets.py``, "Where the layout is fixed": the table is a flat
``uint64[cap]`` from ``wavefront_init`` to the end of ``wavefront_run``, in
the snapshot and on the host; inside the step's loop body only the
membership loop's row gather (through a ``[cap / 128, 128]`` view that is a
bitcast on the TPU) and the chunked scatters touch it.  Pinned here:

 - on the traced step program: no other equation of the loop body has an
   operand or a result of ``cap`` elements;
 - on the step program compiled for a described v5e: how many operations of
   the loop body produce ``cap`` elements (the old body's count beside it);
 - ``bucket_insert`` against the gathering reference of ``test_buckets.py``,
   bit for bit on the table's contents, at the benchmark cells' lane shapes;
 - on the same compiled step: the append moves ``qchunk`` rows a trip,
   never the ``cand``-wide window but for a wide payload's one gather
   before the loop; on the mesh step compiled for the described 2x2 the
   same loop, written by row index, with nothing of a queue column's or a
   shard's size in the stage;
 - on a wide-rowed twin's compiled step: no copy of the payload queue, and
   every operation of a block's size under the stage its predecessor's
   module filed it under.

The host boundaries see the same flat table as before, so their tests are
the ones the repo had: ``tests/test_checkpoint.py`` (the snapshot's table is
flat and bucket-major, and resumes; growth up the ladder keeps the work).
"""

import functools
import re
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.buckets import (
    ROW_LANES,
    SLOTS,
    bucket_of,
    bucket_split,
    parent_chains,
)
from stateright_tpu.ops.hashing import EMPTY
from stateright_tpu.parallel import wavefront as wf
from stateright_tpu.parallel.carry import carry_avals

sys.path.insert(0, str(Path(__file__).parent))
from hlo_stage import stage_movers  # noqa: E402
from test_buckets import INSERTS, both_inserts, fresh  # noqa: E402


def fps_in_bucket(count, nbuckets, bucket=0):
    """``count`` distinct fingerprints the derivation places in ``bucket``."""
    x = np.arange(1, 64 * count * nbuckets, dtype=np.uint64)
    found = x[bucket_of(x, nbuckets) == bucket][:count]
    assert len(found) == count
    return found


# -- the traced step program -------------------------------------------------


def sub_jaxprs(eqn):
    for value in eqn.params.values():
        for x in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def table_sized_eqns(jaxpr, cap, inside=False):
    """``(primitive, shapes)`` of every equation INSIDE a ``while`` body (at
    any depth) that has an operand or a result of ``cap`` or more elements;
    an equation that only carries them into a sub-program (a loop, a call)
    is descended into, not listed."""
    for eqn in jaxpr.eqns:
        subs = list(sub_jaxprs(eqn))
        if subs:
            for sub in subs:
                yield from table_sized_eqns(
                    sub, cap, inside or eqn.primitive.name == "while"
                )
            continue
        shapes = [
            tuple(v.aval.shape)
            for v in (*eqn.invars, *eqn.outvars)
            if getattr(getattr(v, "aval", None), "size", 0) >= cap
        ]
        if inside and shapes:
            yield eqn.primitive.name, shapes


def step_program(n=3, cap=1 << 16, qcap=1 << 10, batch=32, cand=256,
                 model=None, **kw):
    model = model or TwoPhaseSys(n)
    tensor, props = model.tensor_model(), list(model.properties())
    _, run_fn = wf._build_engine(
        tensor, props, cap, qcap, batch, 8, None, cand=cand, **kw
    )
    return run_fn, carry_avals(tensor, len(props), cap, qcap, batch, False)


@pytest.mark.parametrize("sym", [False, True])
def test_the_loop_body_touches_the_table_with_a_gather_and_scatters_only(sym):
    """POR off.  The two table arrays enter the step's ``while`` as carries
    and meet four kinds of equation there: the row view (flat against
    ``[cap / ROW_LANES, ROW_LANES]``, nothing else), the membership loop's
    gather FROM that view, and the write loop's two scatters.  No
    ``convert``, ``select_n``, ``copy``, ``transpose`` or second view of
    ``cap`` elements: each would be a pass over the table a step."""
    cap = 1 << 16
    run_fn, avals = step_program(cap=cap, sym=sym)
    found = list(table_sized_eqns(jax.make_jaxpr(run_fn)(avals).jaxpr, cap))
    rows = (cap // ROW_LANES, ROW_LANES)
    assert sorted(found) == sorted([
        ("reshape", [(cap,), rows]),
        ("gather", [rows]),
        ("scatter", [(cap,), (cap,)]),
        ("scatter", [(cap,), (cap,)]),
    ])


# -- the step program compiled for the chip ----------------------------------


@pytest.fixture(scope="module")
def v5e_2x2():
    """The devices of a described (not attached) v5e 2x2 to compile for;
    described inside the fixture, never at import, and skipped where it
    cannot be."""
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_v5e_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2[0])


HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[a-z0-9]+\[[^=]*?)\s([a-z\-]+)\("
)
HLO_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
# what names or forwards a buffer without producing one
HLO_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def table_sized_operations(hlo_text, cap, in_entry=False, op_names=False):
    """``(opcode, result shape)`` of every operation outside the entry
    computation and outside fused computations (a fusion counts once, as
    the operation it is) whose result holds ``cap`` or more elements: with
    one ``while`` at the top of the run program, these are the operations
    of its body and of the loops nested in it.  ``in_entry``: those of the
    entry computation instead (a custom call under its target's name).
    ``op_names``: each with its ``op_name`` as a third member ("" for
    none)."""
    found, entry, name = [], False, ""
    for line in hlo_text.splitlines():
        head = HLO_COMPUTATION.match(line)
        if head:
            entry, name = bool(head.group(1)), head.group(2)
            continue
        m = HLO_INSTRUCTION.match(line)
        if not m or entry != in_entry or "fused_computation" in name:
            continue
        shape, opcode = m.groups()
        sizes = [
            int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            for dims in re.findall(r"[a-z0-9]+\[([0-9,]*)\]", shape)
        ]
        if opcode not in HLO_NO_BUFFER and max(sizes, default=0) >= cap:
            target = re.search(r'custom_call_target="(\w+)"', line)
            found.append((target.group(1) if target else opcode, shape.strip()))
            if op_names:
                op_name = re.search(r'op_name="([^"]*)"', line)
                found[-1] += (op_name.group(1) if op_name else "",)
    return found


def compiled_for(sharding, fn, *avals, **static):
    """``fn`` compiled ahead of time for the described chip, the persistent
    cache off around it (an entry written for a chip that is not attached
    cannot be read back, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    if sharding is not None:  # None: the avals carry their own
        avals = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            avals,
        )
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fn.lower(*avals, **static).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


BIG = dict(cap=1 << 23, qcap=1 << 14, batch=256, cand=2048)


@pytest.fixture(scope="module")
def big_step(one_v5e_chip):
    """The 2pc step program at a 2^23-slot table, compiled ahead of time
    for one v5e: ONE compile for the tests that read its text."""
    run_fn, avals = step_program(**BIG)
    return compiled_for(one_v5e_chip, run_fn, avals)


def test_the_compiled_loop_holds_two_table_sized_operations(big_step):
    """The 2pc step program at a 2^23-slot table, compiled ahead of time
    for one v5e: inside the loop TWO operations produce ``cap`` elements,
    the write loop's two scatter fusions (each a two-plane ``u32[cap]``
    scatter, in place).  The parent's body (PR 37's tree, compiled the
    same way) held NINE: the same two, plus two ``reshape -> [nbuckets,
    16]``, two ``copy`` into the gather's slot-major layout, and a
    ``copy-start`` / ``copy-done`` / ``ConcatBitcast`` moving a plane
    between memories around them."""
    cap, compiled = BIG["cap"], big_step
    found = table_sized_operations(compiled.as_text(), cap)
    assert [op for op, _ in found] == ["fusion", "fusion"], found  # parent: 9
    assert all(shape.count(f"u32[{cap}]") == 2 for _, shape in found)
    # and the program holds no transient copy of the table: its planes are
    # 4 x 32 MiB, and the parent's temporaries were 405.8 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_compiled_append_moves_a_chunk_a_trip(big_step):
    """The same module's ``sr.append``: the TPU compiler's gathers
    and update slices of the append are ``qchunk`` = ``batch`` = 256 rows
    (the fingerprint's two ``u32`` planes a gather each), none the 2,048
    lanes of a ``cand``-wide window, and
    the loop around them kept the stage's name."""
    movers = stage_movers(big_step.as_text(), "sr.append")
    assert {kind for kind, _ in movers} == {"gather", "dynamic-update-slice"}
    assert {rows for _, rows in movers} == {BIG["batch"]}
    assert "sr.append/while/body" in big_step.as_text()


WIDE = dict(cap=1 << 16, qcap=1 << 14, batch=256, cand=1024)


def wide_step_text(sharding):
    """paxos-2's step (22-word rows, 20 actions: a 5,120-row candidate
    block) compiled for one v5e: the module's text, and the payload
    buffer's ``(qalloc, width)`` and the block's ``(m, width)``."""
    from stateright_tpu.models.paxos import paxos_model

    model = paxos_model(2)
    run_fn, avals = step_program(model=model, **WIDE)
    m = WIDE["batch"] * model.tensor_model().max_actions
    return (compiled_for(sharding, run_fn, avals).as_text(), avals.q_rows.shape,
            (m, avals.q_rows.shape[1]))


@pytest.fixture(scope="module")
def wide_step(one_v5e_chip):
    return wide_step_text(one_v5e_chip)


def whole_queue_copies(text, queue):
    return [line for line in text.splitlines()
            if re.search(rf"= \w+\[{queue[0]},{queue[1]}\]\S* copy(-start)?\(", line)]


# a buffer moved between the chip's memories, not an operation of the step:
# the compiler schedules these around any program and names none of them
HLO_MEMORY_MOVES = {"copy-start", "copy-done", "slice-start", "slice-done"}


def block_sized_operations(text, elements):
    """``(opcode, result shape, op_name)`` of every operation of the run
    program's loops (``table_sized_operations``'s scope) that produces at
    least ``elements`` elements, the moves between memories apart."""
    return [found for found in table_sized_operations(
                text, elements, op_names=True)
            if found[0] not in HLO_MEMORY_MOVES]


def test_a_wide_rowed_queue_is_never_copied_whole(
        wide_step, one_v5e_chip, monkeypatch):
    """paxos-2's step compiled for one v5e: no ``copy`` of the whole
    payload buffer anywhere in the run program - the queue keeps the
    layout it arrives in (one plane a word) through the append's loop,
    because a chunk is handed to the update slice in that order
    (``wavefront._in_column_planes``).  Without the pin the compiler carries
    the queue row-major (22 words padded to 128) and copies it whole for
    every pop: five copies, two of them a step (PERF.md section 6, PR 53)."""
    text, queue, _ = wide_step
    assert whole_queue_copies(text, queue) == []
    monkeypatch.setattr(wf, "_in_column_planes", lambda rows: rows)
    text, queue, _ = wide_step_text(one_v5e_chip)
    assert len(whole_queue_copies(text, queue)) == 5  # what the pin is there for


def while_only_append(bufs, tail0, sel, n_new, cands, qchunk, place=None):
    """``append_novel`` with the payload gathered inside the loop like the
    columns: no gather reads the candidate block outside a ``while``."""
    last = jnp.int32(sel.shape[0] - qchunk)

    def chunk(state):
        k, bufs = state
        off = jnp.minimum(k * qchunk, last)
        w_idx = jax.lax.dynamic_slice(sel, (off,), (qchunk,))
        return k + 1, tuple(
            jax.lax.dynamic_update_slice(
                q, wf._in_column_planes(c[w_idx]) if q.ndim == 2 else c[w_idx],
                (tail0 + off,) + (jnp.int32(0),) * (q.ndim - 1))
            for q, c in zip(bufs, cands))

    n_chunks, bufs = jax.lax.while_loop(
        lambda s: s[0] * qchunk < n_new, chunk, (jnp.int32(0), tuple(bufs)))
    return bufs, n_chunks


def test_every_pass_over_the_candidate_block_carries_its_stages_name(
        wide_step, one_v5e_chip, monkeypatch):
    """The same module: every operation of the step that produces at least
    the smallest of the step's blocks - the candidate block's ``m x width``
    words, its slot part ``batch x actions x slots``, the gathered payload's
    ``cand x width`` - carries an ``op_name`` under one of the ``sr.``
    stages, and the stage its predecessor's module gave it (commit fa77ed2,
    compiled the same way), so the profile's reader
    (``benchmarks/srbench/xstages.py``) files the same seconds under the
    same stage on both sides of this change:

     - the block's relayout for the append's row gather - a ``copy`` into
       row-major ``[batch, actions, width]`` and the ``reshape`` to ``[m,
       width]``, a plane each of a ``u64`` - is ``sr.hash``'s;
     - the slot block's two copies into the hash's order are ``sr.hash``'s;
     - the payload's gather is ``sr.append``'s, before the loop, and what
       it gathered takes no further pass (the predecessor copied it whole
       into plane order, under ``sr.append`` too): the loop slices it as
       it lies (``wavefront._chunk_rows``).

    The one operation without a name is the slot sort's ``iota``, which has
    none at the predecessor either.  With the payload gathered INSIDE the
    loop the relayout's two passes run under no name (what
    ``while_only_append`` compiles to): why ``append_novel`` gathers a wide
    payload before it."""
    batch, cand = WIDE["batch"], WIDE["cand"]

    def passes(text, block):
        (m, width), actions = block, block[0] // batch
        slots = (batch, actions, actions)  # paxos-2: a slot an action
        ops = block_sized_operations(
            text, min(m * width, int(np.prod(slots)), cand * width))
        assert ops

        def named(shape_prefix, *opcodes):
            return [name for op, shape, name in ops
                    if op in opcodes and shape.startswith(shape_prefix)]

        return ops, {
            "relayout": named(f"u32[{batch},{actions},{width}]", "copy")
            + named(f"u32[{m},{width}]", "reshape"),
            "slots": named("u32[%d,%d,%d]" % slots, "copy"),
            "gathered": named(f"u32[{cand},{width}]", "fusion", "copy"),
        }

    text, _, block = wide_step
    ops, found = passes(text, block)
    assert [(op, shape) for op, shape, name in ops
            if "/sr." not in name and op != "iota"] == []
    assert len(found["relayout"]) == 4
    assert all("/sr.hash/" in name for name in found["relayout"])
    assert len(found["slots"]) == 2
    assert all("/sr.hash/" in name for name in found["slots"])
    assert len(found["gathered"]) == 2  # the gather alone, a plane each
    assert all(name.endswith("/sr.append/gather") for name in found["gathered"])
    monkeypatch.setattr(wf, "append_novel", while_only_append)
    text, _, block = wide_step_text(one_v5e_chip)
    _, found = passes(text, block)
    assert len(found["relayout"]) == 4
    assert all(name == "" for name in found["relayout"])


# the window's module (commit 17a2c16: one ``cand``-wide window a step on the
# mesh), compiled the same way at the same shapes: temporaries and generated
# code a chip, and the whole-shard copies of the payload buffer
MESH_WINDOW = {
    "one_word": dict(temp=1_936_896, code=3_587_072, shard_copies=0),
    "wide": dict(temp=3_098_624, code=4_704_768, shard_copies=3),
}


@pytest.mark.parametrize("rows", ["one_word", "wide"])
def test_the_mesh_append_compiled_for_the_2x2_moves_a_chunk_a_trip(v5e_2x2, rows):
    """The mesh step (batch 64, cand 512; 2pc-3's one-word rows and
    paxos-2's 22-word ones) compiled for the described four chips runs
    ``append_novel``'s loop, the one chip's:

     - ``sr.append/while/body`` is there, and inside it every gather and
       every scatter of the stage is ``qchunk`` = 64 rows - four scatters by
       row index, one a buffer, and NO update slice (an update slice of a
       sharded buffer is an all-gather of it, a trip: ``StepPlacement.
       append``) - and the one collective is the chunk's words made whole,
       ``qchunk`` of them a plane;
     - outside the loop the stage holds nothing for one-word rows, and for
       wide ones the payload's one ``cand``-row gather (a plane each of a
       ``u64``) and the all-reduce that makes it whole, ``cand x width``
       words - the window's own two, by name;
     - nothing of the stage moves a queue column or a shard of one, and the
       payload buffer's shard is copied whole no oftener than under the
       window (at this size: once as the argument, twice at the run loop's
       edge, never inside a loop) with the chunk handed to the scatter as it
       lies, no ``_in_column_planes``;
     - the run program's temporaries stay within 256 KiB of the window's
       (a chunk's buffers; at the benchmark's shapes they are 254 MB UNDER
       it, PERF.md section 6) and its code within 64 KiB (the wide block's
       flattening, before the loop)."""
    from jax.sharding import Mesh

    from stateright_tpu.models.paxos import paxos_model
    from stateright_tpu.parallel.mesh import MeshTpuChecker
    from stateright_tpu.parallel.partition import (
        MESH_AXES,
        StepPlacement,
        replicated,
    )
    from stateright_tpu.telemetry.collectives import hlo_collectives

    mesh = Mesh(np.asarray(v5e_2x2).reshape(1, 4), MESH_AXES)
    cap, qcap, batch, cand = 1 << 14, 1 << 16, 64, 512
    run_fn, avals = step_program(
        cap=cap, qcap=qcap, batch=batch, cand=cand, place=StepPlacement(mesh),
        model=paxos_model(2) if rows == "wide" else None)
    placer = MeshTpuChecker.__new__(MeshTpuChecker)  # its rules, no engine
    placer._mesh = mesh
    placed = placer._place(avals)
    mesh_run = jax.jit(run_fn, in_shardings=(placed,),
                       out_shardings=(placed, replicated(mesh)))
    avals = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        avals, placed)
    compiled = compiled_for(None, mesh_run, avals)
    text = compiled.as_text()
    qalloc, width = avals.q_rows.shape
    assert (width > 1) == (rows == "wide")
    assert "sr.append/while/body" in text
    qchunk = batch
    inside = Counter(stage_movers(text, "sr.append/while/body"))
    assert inside[("scatter", qchunk)] == 4
    assert {kind for kind, _ in inside} == {"gather", "scatter", "all-reduce"}
    assert {n for _, n in inside} == {qchunk}
    outside = Counter(stage_movers(text, "sr.append")) - inside
    assert outside == (
        {("gather", cand): 2, ("all-reduce", cand * width): 1}
        if rows == "wide" else {})
    assert cand * width < qalloc // 4  # so: nothing the size of a column's shard
    if rows == "wide":  # the step's largest collective, and whose it is
        largest = hlo_collectives(text)["largest"]
        assert f"u32[{cand},{width}]" in largest["shape"]  # combined, here
        assert largest["op"].endswith("/while/body/sr.append/gather")
    window = MESH_WINDOW[rows]
    shard_copies = whole_queue_copies(text, (qalloc // 4, width))
    assert len(shard_copies) == window["shard_copies"]
    assert not [line for line in shard_copies if "/while/body/" in line]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= window["temp"] + (256 << 10)
    assert mem.generated_code_size_in_bytes <= window["code"] + (64 << 10)


def test_the_parent_walk_reads_the_table_with_two_slices_a_link():
    """``parent_chains`` as traced: inside its loops the two table arrays
    meet ONE kind of equation, the ``SLOTS``-wide ``dynamic_slice`` of a
    link's bucket.  No view, gather, compare or copy of ``cap`` elements:
    what the walk touches grows with the paths, not with the table."""
    cap = 1 << 16
    table = jax.ShapeDtypeStruct((cap,), jnp.uint64)
    starts = jax.ShapeDtypeStruct((3,), jnp.uint64)
    jaxpr = jax.make_jaxpr(parent_chains, static_argnums=3)(
        table, table, starts, 32
    ).jaxpr
    # inside=True: the whole program, not its loops alone
    assert sorted(table_sized_eqns(jaxpr, cap, inside=True)) == [
        ("dynamic_slice", [(cap,)]), ("dynamic_slice", [(cap,)]),
    ]


def test_the_compiled_parent_walk_adds_no_table_sized_operation(one_v5e_chip):
    """``parent_chains`` at a 2^24-slot table (2 x 128 MiB), compiled ahead
    of time for one v5e.  Its loops hold NO operation of ``cap`` elements:
    no copy, no reshape, no relayout of a plane.  Its entry holds four, and
    they are not the walk's: the TPU has no 64-bit words, so EVERY program
    that takes a ``u64`` argument first splits it into two ``u32`` planes
    (``X64SplitLow`` / ``X64SplitHigh``; the run program does that to its
    whole carry, the table included, on every device call).  One pass over
    the table at the chip's memory speed, where the host path pulled it at
    200-245 MB/s and built a dict of it; the planes are its only
    temporaries, a subset of what the run program's calls already held."""
    cap = 1 << 24
    table = jax.ShapeDtypeStruct((cap,), jnp.uint64)
    starts = jax.ShapeDtypeStruct((4,), jnp.uint64)
    compiled = compiled_for(
        one_v5e_chip, parent_chains, table, table, starts, bound=32
    )
    text = compiled.as_text()
    assert table_sized_operations(text, cap) == []
    assert sorted(op for op, _ in table_sized_operations(text, cap, in_entry=True)) == [
        "X64SplitHigh", "X64SplitHigh", "X64SplitLow", "X64SplitLow",
    ]
    mem = compiled.memory_analysis()
    planes = 4 * 4 * cap  # two arrays, two u32 planes each
    assert mem.temp_size_in_bytes <= planes + (1 << 20)  # read: 3 planes + 0.4 MB
    assert mem.output_size_in_bytes < 64 << 10 and mem.alias_size_in_bytes == 0


# -- growth where the carry lies (PR 48) -------------------------------------


def test_the_compiled_split_is_compares_and_selects_alone(one_v5e_chip):
    """``bucket_split`` from 2^22 to 2^23 slots (``paxos3-defaults``' last
    rung), compiled ahead of time for one v5e: no ``sort`` (~10 s of
    compile an operand past 16,384 lanes, PR 36), no ``scatter``, no
    ``gather``, no loop; besides the entry's ``u64`` plane splits and combines
    it is fusions.  Its temporaries are the planes of what it reads and
    writes and the ``[nbuckets, 32, 16]`` select, fused: under three times
    the new table (read here: 185.6 MB against 134.2 MB of output)."""
    cap = 1 << 22
    table = jax.ShapeDtypeStruct((cap,), jnp.uint64)
    compiled = compiled_for(
        one_v5e_chip, bucket_split, table, table,
        new_nbuckets=2 * cap // SLOTS,
    )
    text = compiled.as_text()
    assert not re.findall(r"\b(sort|scatter|gather|while)\b", text)
    assert sorted(
        op for op, _ in table_sized_operations(text, cap, in_entry=True)
        if op.startswith("X64")
    ) == ["X64Combine", "X64Combine", "X64SplitHigh", "X64SplitHigh",
          "X64SplitLow", "X64SplitLow"]
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * 8 * 2 * cap
    assert mem.temp_size_in_bytes < 3 * 8 * 2 * cap


@pytest.mark.parametrize("grown", [False, True])
def test_the_compiled_slide_holds_the_planes_and_no_more(one_v5e_chip, grown):
    """The slide of paxos-3's queue (33 ``u64`` a row, 2^19 rows and one
    append window) at ``paxos3-defaults``' last rung: one pass a buffer,
    no loop, no gather.  In place its outputs are its donated inputs and
    its temporaries are the ``u32`` planes of what it reads and of what it
    writes (2 x the buffers); into doubled buffers, the planes of what it
    writes (1 x the new buffers).  What a growth event holds at most is
    read off these: ``_grow_on_device`` runs the queue before the table."""
    m, width = 2048 * 30, 33
    new = (1 << 19) + m
    old = (1 << 18) + m if grown else new
    slide = wf._slide_queue_grown if grown else wf._slide_queue_in_place

    def lanes(dtype, *rest):
        return jax.ShapeDtypeStruct((old, *rest), dtype)

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = compiled_for(
        one_v5e_chip, slide,
        lanes(jnp.uint64, width), lanes(jnp.uint64), lanes(jnp.uint32),
        lanes(jnp.uint32), scalar, scalar, qalloc=new,
    )
    assert not re.findall(r"\b(sort|scatter|gather|while)\b", compiled.as_text())
    mem = compiled.memory_analysis()
    out = mem.output_size_in_bytes  # the rows are tiled to 40 words: 187 MB
    assert out >= new * (width * 8 + 8 + 4 + 4)
    aliased = mem.alias_size_in_bytes
    if grown:
        assert aliased == 0
    else:  # all of it but the output tuple's index table (512 B)
        assert out - 4096 < aliased <= out
    assert mem.temp_size_in_bytes <= (1 if grown else 2) * out + (1 << 20)


# -- bucket_insert at the cells' shapes --------------------------------------

# (candidate lanes = batch x actions, the ``cand`` budget, window = batch,
# log2 of the table) of the five presized configurations; the tables are
# 2^6 smaller here (the lanes are what the row view, the masks and the
# window paddings see; the table's size only sets the bucket bits)
CELL_SHAPES = {
    "twopc8": (2048 * 42, 32768, 2048, 23 - 6),
    "paxos3": (4096 * 30, 16384, 4096, 23 - 6),
    "linreg2x3o": (4096 * 20, 16384, 4096, 21 - 6),
    "twopc13sym": (1024 * 67, 32768, 1024, 21 - 6),
    "singlecopy4": (4096 * 20, 16384, 4096, 22 - 6),
}


@functools.lru_cache(maxsize=None)
def seeded_cell(cell):
    """The cell's table, a third of a pool of ``cap / 16`` fingerprints in
    it already: ``(table_fp, table_payload, pool)``."""
    _, _, window, logcap = CELL_SHAPES[cell]
    cap = 1 << logcap
    rng = np.random.default_rng(0)
    pool = rng.integers(1, 1 << 62, cap // 16).astype(np.uint64)
    seeded = jnp.asarray(pool[: len(pool) // 3])
    tfp, tpl, _, n, ovf, _ = INSERTS["new"](
        *fresh(cap // SLOTS), seeded, seeded + jnp.uint64(7), window=window
    )
    assert int(n) == len(seeded) and not bool(ovf)
    return tfp, tpl, pool


def cell_batch(cell, budget):
    """A candidate batch shaped like the cell's: the valid lanes drawn from
    the pool with repeats (as a step's candidates are) and scattered over
    the ``m`` lanes; ``budget`` puts their number under, at or over the
    ``cand`` budget.  ``(fps, payloads)``."""
    m, cb, _, _ = CELL_SHAPES[cell]
    rng = np.random.default_rng(1)
    n_valid = {"under": cb - 37, "at": cb, "over": cb + 1}[budget]
    fps = np.full(m, EMPTY, np.uint64)
    lanes = np.sort(rng.choice(m, n_valid, replace=False))
    fps[lanes] = rng.choice(seeded_cell(cell)[2], n_valid)
    return fps, np.arange(1, m + 1, dtype=np.uint64)


@pytest.mark.parametrize("budget", ["under", "at", "over"])
@pytest.mark.parametrize("order", [False, True], ids=["table", "generation"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_insert_equals_the_reference_at_the_cells_shapes(cell, order, budget):
    """Both tables (in the flat form every host boundary sees),
    ``sel[:n_new]``, ``n_new`` and both flags equal to the gathering
    reference's (``test_buckets.both_inserts``); over the budget nothing
    is written."""
    _, cb, window, _ = CELL_SHAPES[cell]
    tfp0, tpl0, _ = seeded_cell(cell)
    fps, pls = cell_batch(cell, budget)
    tfp, _, _, n_new, ovf, covf = both_inserts(
        tfp0, tpl0, fps, pls, order, cb, window
    )
    assert tfp.shape == tfp0.shape and tfp.dtype == jnp.uint64
    assert not bool(ovf) and bool(covf) == (budget == "over")
    if budget != "over":
        held = set(np.asarray(tfp0)[np.asarray(tfp0) != EMPTY].tolist())
        assert int(n_new) == len(set(fps[fps != EMPTY].tolist()) - held) > 0


@pytest.mark.parametrize("order", [False, True], ids=["table", "generation"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_a_bucket_overflow_returns_the_table_unchanged(cell, order):
    """One bucket driven past ``SLOTS`` among a cell-shaped batch: the flag
    is raised and not one word of either array differs (``both_inserts``
    compares them with what went in) - in the crowded bucket's row, whose
    seven other buckets the fetch brought along, either."""
    _, cb, window, _ = CELL_SHAPES[cell]
    tfp0, tpl0, _ = seeded_cell(cell)
    fps, pls = cell_batch(cell, "under")
    crowd = fps_in_bucket(SLOTS + 1, tfp0.shape[0] // SLOTS)
    fps[np.flatnonzero(fps == EMPTY)[: SLOTS + 1]] = crowd
    out = both_inserts(tfp0, tpl0, fps, pls, order, cb, window)
    assert bool(out[4]) and not bool(out[5]) and int(out[3]) == 0


def test_a_row_holds_eight_buckets_and_a_candidate_sees_its_own():
    """The fetch brings ``ROW_LANES / SLOTS`` buckets along.  A candidate
    whose fingerprint already sits in a NEIGHBOUR bucket of its row (it
    cannot, by the bucket derivation - so the table is forged) is still
    novel in its own, and its slot is its own bucket's count, not the
    row's."""
    nbuckets = 64
    per_row = ROW_LANES // SLOTS
    assert per_row == 8
    mine, neighbour = 8 * 3 + 2, 8 * 3 + 5  # same row, other bucket
    (fp,) = fps_in_bucket(1, nbuckets, bucket=mine)
    filler = fps_in_bucket(3, nbuckets, bucket=neighbour)
    tfp = np.full(nbuckets * SLOTS, EMPTY, np.uint64)
    tpl = np.zeros(nbuckets * SLOTS, np.uint64)
    tfp[neighbour * SLOTS: neighbour * SLOTS + 4] = [*filler, fp]  # forged
    out = INSERTS["new"](
        jnp.asarray(tfp), jnp.asarray(tpl), jnp.asarray(np.array([fp])),
        jnp.asarray(np.array([9], np.uint64)), window=8,
    )
    assert int(out[3]) == 1 and not bool(out[4])
    assert int(np.asarray(out[0])[mine * SLOTS]) == int(fp)
    assert int(np.asarray(out[1])[mine * SLOTS]) == 9
