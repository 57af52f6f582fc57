"""Mechanical actor-system → tensor-form compiler.

Round 1 proved actor systems can run on the wavefront engine with a
hand-written 700-line device twin per protocol (``models/paxos_tensor.py``).
This module makes that a *capability*: it compiles Python actor handlers
into table-driven jittable ``step_rows`` mechanically, for two fragments
(reference transition semantics being compiled: ``src/actor/model.rs:187-306``):

 - the **register workload** (reference ``src/actor/register.rs``): protocol
   servers + ``RegisterClient(put_count=1)`` clients, a
   linearizability-tester history, and the standard
   linearizable/value-chosen properties;
 - the **general fragment** (round 4): any bounded actor system with
   ``init_history=None`` — including **timeout-driven** actors (timer bits
   in the row, one Timeout action per armed actor, ``SetTimer``/
   ``CancelTimer`` effects tabulated with last-command-wins semantics) —
   whose properties are factored predicates
   (``actor/device_props.py``), tabulated per actor (or actor pair) over
   the compiled state universes.  ``models/raft.py`` is the showcase.

Both fragments support all three network semantics (non-duplicating
multiset, duplicating set, per-pair ordered FIFO), optionally lossy.

How: a bounded host-side closure co-enumerates

 - per-actor reachable state universes ``S_i`` (states become small integer
   codes),
 - the envelope universe ``E`` (envelopes become slot codes for the
   sorted-slot multiset network of ``actor_tensor.py``), and
 - the transition relation ``T_i[s, e] -> (s', sends…)`` by *running each
   actor's real ``on_msg`` handler once per (state, envelope) pair* —
   the handlers never run on device, only their tabulated effects do.

The closure over-approximates reachability (it pairs every known state with
every known envelope), which is what makes it cheap — but means protocols
whose field domains grow with context (Paxos ballots, ABD sequencers) need a
``state_bound`` predicate to cut the divergent tail.  Transitions that would
leave the bound are marked *poison*; executing one on device sets a poison
bit in the row, and parity tests guarantee bounded configurations never
poison (the bound only cuts over-approximation, not real reachability).

History (the linearizability tester) is not table-driven per transition —
its joint state is factored into per-thread fields updated arithmetically on
device, with the ``linearizable`` verdict precomputed per joint history
state (:mod:`.history_tensor`).  The two standard register-workload
properties are recognized by name: ``linearizable`` (ALWAYS, history
verdict lookup) and ``value chosen`` (SOMETIMES, a non-null ``get_ok`` in
flight — reference ``examples/paxos.rs:255-262``).
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Optional

import numpy as np

from ..actor import Id, SetTimer, CancelTimer, Out, Send
from ..actor.model import ActorModel, ActorModelState, _default_boundary
from ..actor.network import (
    Envelope,
    OrderedNetwork,
    UnorderedDuplicatingNetwork,
    UnorderedNonDuplicatingNetwork,
)
from ..actor.register import NULL_VALUE, RegisterClient
from ..semantics import LinearizabilityTester
from .actor_tensor import (
    COUNT_BITS,
    COUNT_MASK,
    SLOT_EMPTY,
    SlotCodec,
    region_send_ordered,
    slot_canonicalize,
    slot_send,
    slot_send_ordered,
)
from .history_tensor import (
    PHASE_DONE,
    PHASE_R_INFLIGHT,
    PHASE_W_INFLIGHT,
    LinHistoryCodec,
    MultiOpLinHistoryCodec,
)
from ..telemetry.spans import (
    PROPS_LIN, TWIN_DROP, TWIN_HISTORY, TWIN_NET, TWIN_TABLE, span,
)
from .tensor_model import (
    BitPacker,
    FieldWriter,
    TensorModel,
    pack_by_rank,
    place_by_rank,
    stable_rank,
)

#: the largest envelope universe whose record is read by compare-and-max
#: (``CompiledActorTensor._env_words``); larger ones are gathered.  Measured
#: alone on a v5e: the select form wins 2.7x at 1,024 envelopes and loses by
#: 4% at 4,096 (PERF.md section 6, PR 44)
_ENV_SELECT_MAX = 2048

#: envelope-kind codes for the history/property tables
_K_OTHER, _K_PUT_OK, _K_GET_OK, _K_PUT_FAIL = 0, 1, 2, 3


def _orl_hint(state) -> str:
    """The cap-error hint for OrderedReliableLink wrapper states: name
    the actually-unbounded fields instead of leaving the user to diff
    200k closure states (the ORL sequencers grow forever unless capped).
    Shared by the exact-cap error and the pre-closure estimate's
    fail-fast error."""
    from ..actor.ordered_reliable_link import LinkState

    if not isinstance(state, LinkState):
        return ""
    return (
        "; this is an OrderedReliableLink wrapper state — "
        "next_send_seq/msgs_pending_ack/last_delivered_seqs "
        "grow without bound when the wrapped actor keeps "
        "sending; cap them with state_bound (worked recipe: "
        "docs/compiling-actor-systems.md, 'Compiling "
        "ORL-wrapped systems')"
    )


class CompileError(Exception):
    """The model is outside the compilable fragment."""


class RecordLayout:
    """Bit layout of one look-up record: named fields packed end to end
    into the least number of 32-bit words their widths need.

    ``fields`` is ``(name, largest value)`` pairs; a field takes
    ``bit_length(largest value)`` bits (at least one) and may straddle two
    words, so ``words == ceil(total bits / 32)`` exactly - a record grows a
    word when a universe outgrows it, never a flag.  :meth:`pack` is numpy
    (the freeze); :meth:`get` takes the record's words as a sequence of
    equally shaped ``uint32`` arrays, numpy or traced alike, and answers
    ``int32`` with shifts and masks alone - no arithmetic, so the interval
    pass (``analysis/interval.py``) bounds a field by its mask."""

    def __init__(self, fields):
        self.layout: dict[str, tuple[int, int]] = {}
        off = 0
        for name, largest in fields:
            bits = max(1, int(largest).bit_length())
            if bits > 31:
                raise CompileError(
                    f"record field {name!r} needs {bits} bits (largest "
                    f"value {largest}); a field is read as one int32"
                )
            self.layout[name] = (off, bits)
            off += bits
        self.bits = off
        self.words = max(1, -(-off // 32))
        assert 32 * (self.words - 1) < max(off, 1) <= 32 * self.words

    def pack(self, **cols) -> np.ndarray:
        """``uint32[N, words]`` from one ``[N]`` integer column a field."""
        assert set(cols) == set(self.layout), (set(cols), set(self.layout))
        n = len(next(iter(cols.values())))
        out = np.zeros((n, self.words), np.uint64)
        for name, (off, bits) in self.layout.items():
            v = np.asarray(cols[name]).astype(np.int64)
            assert v.shape == (n,), (name, v.shape)
            assert ((v >= 0) & (v < (1 << bits))).all(), name
            w, s = divmod(off, 32)
            v = v.astype(np.uint64)
            out[:, w] |= (v << np.uint64(s)) & np.uint64(0xFFFFFFFF)
            if s + bits > 32:
                out[:, w + 1] |= v >> np.uint64(32 - s)
        return out.astype(np.uint32)

    def get(self, words, name: str):
        off, bits = self.layout[name]
        w, s = divmod(off, 32)
        v = words[w] >> np.uint32(s) if s else words[w]
        if s + bits > 32:
            v = v | (words[w + 1] << np.uint32(32 - s))
        if s + bits != 32:
            v = v & np.uint32((1 << bits) - 1)
        return v.astype(np.int32)


def compile_actor_model(
    model: ActorModel,
    *,
    state_bound: Optional[Callable] = None,
    env_bound: Optional[Callable] = None,
    n_slots: Optional[int] = None,
    max_states_per_actor: int = 200_000,
    max_envelopes: int = 100_000,
    max_history_states: int = 2_000_000,
    per_channel: Optional[bool] = None,
    per_channel_depth: Optional[int] = None,
) -> "CompiledActorTensor":
    """Compile ``model`` to a :class:`TensorModel`; raises
    :class:`CompileError` when the model is outside the supported fragment
    (callers typically catch it and fall back to CPU checking).

    ``state_bound(actor_index, state) -> bool`` /
    ``env_bound(envelope) -> bool`` cut the closure's over-approximation for
    protocols with context-dependent domains; transitions crossing the bound
    poison the row on device rather than silently diverging.

    ``per_channel`` selects the network packing (default None: the model's
    ``per_channel_()`` builder state, else ``STATERIGHT_TPU_PER_CHANNEL=1``):
    False = the global sorted-slot multiset; True = one slot region per
    directed ``(src, dst)`` channel, sized to that channel's envelope
    universe — wider rows, but delivery writes become statically confined,
    the independence analysis decomposes the action stack (no ``JX302``),
    and ``por()`` gets real reduction (``docs/analysis.md``).

    ``per_channel_depth`` raises each ORDERED channel's region capacity to
    at least this many slots: an ordered flow can hold the SAME message at
    several ranks (retransmits), which needs more slots than the channel's
    distinct-code count.  The default (the code count) poisons LOUDLY when
    exceeded — never silently diverging — and unordered regions ignore the
    knob (their capacity is already exact).
    """
    with span("twin_compile", None) as compile_span:
        twin = CompiledActorTensor(
            model,
            state_bound=state_bound,
            env_bound=env_bound,
            n_slots=n_slots,
            max_states_per_actor=max_states_per_actor,
            max_envelopes=max_envelopes,
            max_history_states=max_history_states,
            per_channel=per_channel,
            per_channel_depth=per_channel_depth,
        )
        compile_span.set(**twin.compile_attrs())
    # whoever asks for the twin first (the preflight audit, a fingerprint,
    # a checker's constructor) does so before any flight recorder exists:
    # the first checker that adopts the twin records the closed span
    # (parallel/_base.py:_init_common)
    twin.compile_span = compile_span.fields
    return twin


class CompiledActorTensor(TensorModel):
    """Table-driven device twin of a register-workload ``ActorModel``."""

    def __init__(
        self,
        model: ActorModel,
        *,
        state_bound,
        env_bound,
        n_slots,
        max_states_per_actor,
        max_envelopes,
        max_history_states,
        per_channel=None,
        per_channel_depth=None,
    ):
        self.model = model
        if per_channel is None:
            # the ONE resolution rule (builder flag, else env knob) lives
            # on ActorModel — compiled inputs are always ActorModels
            per_channel = model.per_channel_resolved()
        self.per_channel = bool(per_channel)
        self._per_channel_depth = per_channel_depth
        #: which row layout packs the network — surfaced by por_status(),
        #: the run report, and the Explorer /.status por block
        self.network_encoding = (
            "per-channel" if self.per_channel else "slot-multiset"
        )
        self._check_fragment()
        # multi-op register workload (put_count >= 2): per-thread op-index
        # history fields + the MultiOpLinHistoryCodec table strategy
        self._multi = not self.general and self._put_count > 1
        # whether the caller declared real bounds (the preflight auditor
        # downgrades growing-domain findings when a bound already cuts them)
        self._has_state_bound = state_bound is not None
        self._has_env_bound = env_bound is not None
        self._state_bound = state_bound or (lambda i, s: True)
        self._env_bound = env_bound or (lambda e: True)
        self._caps = (max_states_per_actor, max_envelopes)

        self.n_actors = len(model.actors)
        if self.general:
            self.clients = []
            self.C = 0
            self.hist = None
        else:
            self.clients = [
                i
                for i, a in enumerate(model.actors)
                if isinstance(a, RegisterClient)
            ]
            self.C = len(self.clients)
            values = [
                RegisterClient.put_value(
                    int(t), model.actors[t].server_count, 0
                )
                for t in self.clients
            ]
            tester_factory = lambda: type(model.init_history)(
                model.init_history.init_ref_obj
            )
            if self._put_count > 1:
                # per-client write scripts, from the SAME value scheme the
                # real workload uses (RegisterClient.put_value) so the
                # codec cannot drift from the actors
                scripts = [
                    [
                        RegisterClient.put_value(
                            int(t), model.actors[t].server_count, k
                        )
                        for k in range(self._put_count)
                    ]
                    for t in self.clients
                ]
                self.hist = MultiOpLinHistoryCodec(
                    self.clients,
                    scripts,
                    NULL_VALUE,
                    tester_factory=tester_factory,
                    max_states=max_history_states,
                )
            else:
                self.hist = LinHistoryCodec(
                    self.clients,
                    values,
                    # the write-once spec models the unset register as None;
                    # the wire protocol's null stays NULL_VALUE (translated
                    # at the get_ok boundary, mirroring the WO
                    # record_returns recorder)
                    None if self._wo else NULL_VALUE,
                    tester_factory=tester_factory,
                    max_states=max_history_states,
                    write_rets=(("write_ok",), ("write_fail",))
                    if self._wo
                    else (("write_ok",),),
                )

        self._closure()
        self._tabulate_properties()
        self._tabulate_boundary()
        # symmetry tables are built LAZILY (see __getattr__): n!-sized
        # permutation tabulation should cost nothing on runs that never
        # call .symmetry()
        self._sym_tables = None
        self._sym_attempted = False

        if self.per_channel:
            if n_slots is not None:
                raise CompileError(
                    "n_slots is a slot-multiset knob; the per-channel "
                    "layout derives each region's capacity from its "
                    "channel's envelope universe"
                )
            self._build_channel_layout()
            self.n_slots = int(sum(self._ch_cap))
            deliver = sum(
                self._ch_cap[ci]
                for ci, (_s, d) in enumerate(self._channels)
                if d < self.n_actors
            )
            self.max_actions = max(
                deliver
                + (self.n_slots if model.lossy else 0)
                + (self.n_actors if self._has_timers else 0),
                1,  # a message-less, timer-less system still needs a
                #     (never-valid) action column for the engine shapes
            )
        else:
            self.n_slots = n_slots if n_slots is not None else max(
                16, 4 * self.n_actors
            )
            self.max_actions = self.n_slots * (2 if model.lossy else 1) + (
                self.n_actors if self._has_timers else 0
            )
        fields = []
        for i in range(self.n_actors):
            bits = max(1, int(np.ceil(np.log2(max(2, len(self._states[i]))))))
            fields.append((f"a{i}", bits))
        for c in range(self.C):
            if self._multi:
                fields.append((f"h{c}_phase", self.hist.phase_bits))
                for m in range(self.hist.K):
                    fields.append((f"h{c}_snap{m}", self.hist.snap_bits))
                fields.append((f"h{c}_rval", self.hist.rval_bits))
            else:
                fields += [
                    (f"h{c}_phase", 2),
                    (f"h{c}_snap", max(1, 2 * (self.C - 1))),
                    (f"h{c}_rval", 3),
                ]
                if self.hist.wfail_bits:
                    fields.append((f"h{c}_wfail", 1))
        if self._has_timers:
            fields.append(("timers", self.n_actors))
        fields.append(("poison", 1))
        self.pk = BitPacker(fields)
        self.pw = self.pk.width
        self.width = self.pw + self.n_slots
        self.codec = SlotCodec(
            self.n_slots,
            lambda env: self._env_code[env],
            lambda code: self._envs[code],
        )
        self._device_consts = None

    def compile_attrs(self) -> dict:
        """What the closure and tabulation came to (the ``twin_compile``
        span's attributes): per-actor state universes, the envelope
        universe, the row, ``table_bytes`` - the bytes of the closure's
        TABULATION, the host's per-actor numpy tables (``_trans_np`` ...
        ``_env_chosen``, the Timeout, boundary and property tables, the
        history verdict table where the codec needs one) - beside what the
        device holds of it: ``device_table_bytes`` (exactly what
        :meth:`_consts` uploads: the two record tables of
        :meth:`_freeze_records` in place of the per-actor ones),
        ``record_words`` (the 32-bit words of one transition record) and
        ``step_gathers`` (the gather equations one deliver block makes at
        slot lanes: the mechanism is static, so it is counted where it is
        decided, and ``tests/test_actor_tensor_ordered.py`` holds the
        traced step to it); and the
        linearizability history the packed word carries: the codec's
        verdict strategy (``closure`` / ``table``; ``none`` for a model
        without a history), its client threads and their bits; whether
        the network loses messages (``lossy``: the Drop columns) and the
        action columns a popped state expands to (``max_actions``)."""
        import jax

        tables = [
            *self._trans_np, *self._sends_np, *self._poison_np,
            self._env_dst, self._env_pair, self._env_kind, self._env_val,
            self._env_chosen,
        ]
        if self._has_timers:
            tables += [
                *self._teff_np, *self._ttrans_np, *self._tsends_np,
                *self._tpoison_np, *self._tbit_np,
            ]
        if self._boundary_np is not None:
            tables += self._boundary_np
        if self.per_channel:
            tables.append(self._chan_of)
        for entry in self._prop_tables:
            if entry is not None:
                t = entry[1]
                tables += t if isinstance(t, list) else list(t.values())
        hist = self.hist
        if hist is not None and hist._table_built:
            tables += [hist.table_keys, hist.table_ok]
        return {
            "actor_states": ",".join(str(len(s)) for s in self._states),
            "envelopes": len(self._envs),
            "n_slots": int(self.n_slots),
            "row_width": int(self.width),
            "table_bytes": int(sum(np.asarray(t).nbytes for t in tables)),
            "device_table_bytes": int(
                sum(
                    t.nbytes
                    for t in jax.tree_util.tree_leaves(self._consts_np())
                    if isinstance(t, np.ndarray)
                )
            ),
            "record_words": int(self._trans_layout.words),
            "step_gathers": int(self._step_gathers()),
            "hist_strategy": "none" if hist is None else hist.strategy,
            "hist_threads": 0 if hist is None else int(hist.C),
            "hist_bits": 0 if hist is None else int(hist.C * hist.thread_bits),
            "lossy": bool(self.model.lossy),
            "max_actions": int(self.max_actions),
        }

    # -- fragment check ------------------------------------------------------

    def _check_fragment(self) -> None:
        m = self.model
        if not isinstance(
            m.init_network,
            (
                UnorderedNonDuplicatingNetwork,
                UnorderedDuplicatingNetwork,
                OrderedNetwork,
            ),
        ):
            raise CompileError(
                "unsupported network semantics: "
                + type(m.init_network).__name__
            )
        self.dup = isinstance(m.init_network, UnorderedDuplicatingNetwork)
        self.ordered = isinstance(m.init_network, OrderedNetwork)
        from ..actor.device_props import FactoredPredicate as _FP

        self._boundary = None
        if m._within_boundary is not _default_boundary:
            # a FACTORED boundary compiles (tabulated like the properties;
            # successors crossing it are masked invalid, mirroring the host
            # checkers' within_boundary filter); arbitrary closures do not
            if isinstance(m._within_boundary, _FP) and m._within_boundary.kind in (
                "forall",
                "exists",
            ):
                self._boundary = m._within_boundary
            else:
                raise CompileError(
                    "within_boundary must be a factored per-actor predicate "
                    "(forall_actors/exists_actor) to compile"
                )
        if m.init_history is None:
            # GENERAL fragment: no auxiliary history; every property must be
            # a factored predicate the compiler can tabulate over the
            # per-actor state universes (``actor/device_props.py``)
            from ..actor.device_props import FactoredPredicate

            self.general = True
            self._wo = False
            self._put_count = 0
            bad = sorted(
                p.name
                for p in m.properties()
                if not isinstance(p.condition, FactoredPredicate)
            )
            if bad:
                raise CompileError(
                    "history-free models need factored properties "
                    "(forall_actors/exists_actor/forall_actor_pairs/"
                    f"exists_actor_pair); non-factored: {bad}"
                )
            return
        self.general = False
        if not isinstance(m.init_history, LinearizabilityTester):
            raise CompileError(
                "history must be a LinearizabilityTester (register "
                "workload), or None for the general fragment"
            )
        from ..actor.device_props import FactoredPredicate as _FP2

        std = {"linearizable", "value chosen"}
        extra_bad = sorted(
            p.name
            for p in m.properties()
            if p.name not in std and not isinstance(p.condition, _FP2)
        )
        names = sorted(p.name for p in m.properties() if p.name in std)
        if names != ["linearizable", "value chosen"] or extra_bad:
            raise CompileError(
                "register workloads compile {'linearizable', 'value "
                "chosen'} plus any number of factored predicates "
                "(actor/device_props.py); got standard="
                + repr(names)
                + " non-factored extras="
                + repr(extra_bad)
            )
        from ..actor.register import record_invocations, record_returns
        from ..actor.write_once_register import (
            record_returns as wo_record_returns,
        )

        if m._record_msg_in is record_returns:
            self._wo = False
        elif m._record_msg_in is wo_record_returns:
            # write-once workload: put_fail completes the write with
            # ("write_fail",) and a null read maps to the spec's None
            self._wo = True
        else:
            # the device history update hard-codes these recorders' semantics
            # (put_ok/put_fail/get_ok -> returns, put/get sends -> invocations)
            raise CompileError(
                "history recorders must be the standard register (or "
                "write-once register) record_returns/record_invocations"
            )
        if m._record_msg_out is not record_invocations:
            raise CompileError(
                "history recorders must be the standard register "
                "record_returns/record_invocations"
            )
        clients = [a for a in m.actors if isinstance(a, RegisterClient)]
        if not clients or any(c.put_count < 1 for c in clients):
            raise CompileError(
                "workload must be RegisterClient actors with put_count >= 1"
            )
        put_counts = {c.put_count for c in clients}
        if len(put_counts) != 1:
            raise CompileError(
                f"per-client put_counts must be uniform (got {sorted(put_counts)})"
            )
        self._put_count = put_counts.pop()
        if self._wo and self._put_count != 1:
            raise CompileError(
                "write-once workloads compile with put_count=1 only (a "
                "failed write changes which op takes effect; the multi-op "
                "codec models write_ok returns)"
            )
        if any(
            isinstance(a, RegisterClient)
            != (i >= len(m.actors) - len(clients))
            for i, a in enumerate(m.actors)
        ):
            raise CompileError("clients must follow servers in the actor list")

    # -- closure -------------------------------------------------------------

    def _closure(self) -> None:
        """Co-enumerate per-actor state universes, the envelope universe, and
        the transition tables by running the real handlers host-side."""
        m = self.model
        n = self.n_actors
        max_s, max_e = self._caps

        self._states: list[list] = [[] for _ in range(n)]  # code -> state
        self._state_code: list[dict] = [{} for _ in range(n)]
        self._envs: list[Envelope] = []  # code -> envelope
        self._env_code: dict[Envelope, int] = {}
        # (i, s_code, e_code) -> (new_s_code | -1, sends, poison, timer_eff)
        # timer_eff: -1 keep, 0 clear, 1 set (last timer command wins,
        # mirroring sequential _process_commands)
        trans: dict[tuple, tuple] = {}
        # (i, s_code) -> (new_s_code, sends, poison, timer_bit) — the
        # Timeout action: reference clears the flag, then commands may
        # re-set it (``model.rs:288-306``); never pruned (``is_no_op &&
        # keep_timer`` is unsatisfiable, so every timeout at least clears
        # the timer)
        ttrans: dict[tuple, tuple] = {}
        work: deque = deque()  # ("s", i, s_code) | ("e", e_code)

        def add_state(i: int, s) -> tuple[int, bool]:
            code = self._state_code[i].get(s)
            if code is not None:
                return code, True
            if not self._state_bound(i, s):
                return -1, False
            code = len(self._states[i])
            if code >= max_s:
                raise CompileError(
                    f"actor {i} state universe exceeded {max_s}; "
                    "tighten state_bound" + _orl_hint(s)
                )
            self._states[i].append(s)
            self._state_code[i][s] = code
            work.append(("s", i, code))
            return code, True

        def add_env(env: Envelope) -> tuple[int, bool]:
            code = self._env_code.get(env)
            if code is not None:
                return code, True
            if not self._env_bound(env):
                return -1, False
            code = len(self._envs)
            if code >= max_e:
                raise CompileError(
                    f"envelope universe exceeded {max_e}; tighten env_bound"
                )
            self._envs.append(env)
            self._env_code[env] = code
            work.append(("e", code))
            return code, True

        # -- fail-fast cap estimate ------------------------------------------
        # The eager closure can burn minutes of handler calls before an
        # actor's universe hits max_s (measured: 85s for the 3-client
        # per-channel paxos closure to FAIL).  Checkpoint every few
        # thousand handler calls: once the largest universe passes
        # max_s/8, extrapolate the recent states-per-call rate over the
        # deliveries ALREADY queued — when that estimate clears the cap
        # with a 2x margin at TWO consecutive checkpoints with a
        # non-decaying rate, raise the cap error in seconds with the
        # measured estimate, instead of grinding to the exact wall.
        # Guarded against converging closures (whose production rate
        # decays as the universe fills: the fleet's largest legit
        # closure, paxos-2 at 4 servers, peaks at 22.5k states and never
        # reaches the max_s/8 = 25k engage threshold): the blowup must
        # already hold an eighth of the cap, keep producing at an
        # undiminished rate across two windows, AND overshoot the cap
        # 2x on queued work alone.  Escape hatch:
        # STATERIGHT_TPU_CLOSURE_ESTIMATE=off.
        import os as _os

        est_env = _os.environ.get(
            "STATERIGHT_TPU_CLOSURE_ESTIMATE", ""
        ).lower()
        est_on = est_env not in ("off", "0")
        est_debug = est_env == "debug"
        calls = 0
        _CHECK_EVERY = 2048
        next_check = _CHECK_EVERY
        # (calls, states) at the previous checkpoint; previous window
        # rate; consecutive over-bar checkpoints
        last_state = [0, 0, 0.0, 0]

        def _estimate_check() -> None:
            sizes = [len(s) for s in self._states]
            big = max(range(n), key=lambda i: sizes[i])
            d_calls = calls - last_state[0]
            d_states = sizes[big] - last_state[1]
            prev_rate = last_state[2]
            rate = d_states / max(d_calls, 1)
            last_state[0], last_state[1] = calls, sizes[big]
            last_state[2] = rate
            if sizes[big] * 8 < max_s:
                last_state[3] = 0
                return
            pending = 0
            env_by_dst = [0] * n
            for env in self._envs:
                d = int(env.dst)
                if d < n:
                    env_by_dst[d] += 1
            for item in work:
                if item[0] == "s":
                    pending += env_by_dst[item[1]]
                else:
                    d = int(self._envs[item[1]].dst)
                    if d < n:
                        pending += sizes[d]
            estimate = sizes[big] + int(rate * pending)
            decaying = prev_rate > 0 and rate < 0.5 * prev_rate
            if est_debug:
                print(
                    f"closure-estimate: states={sizes[big]} calls={calls} "
                    f"rate={rate:.3f} pending={pending} "
                    f"estimate={estimate} decaying={decaying} "
                    f"streak={last_state[3]}"
                )
            if estimate > 2 * max_s and not decaying:
                last_state[3] += 1
            else:
                last_state[3] = 0
            if last_state[3] >= 2:
                raise CompileError(
                    f"actor {big} state universe is on course to exceed "
                    f"the {max_s}-state cap: {sizes[big]} states after "
                    f"{calls} handler calls with {pending} deliveries "
                    f"already queued, production rate undiminished "
                    f"(pre-closure estimate ≥ {estimate}); "
                    "tighten state_bound, or raise max_states_per_actor "
                    "(escape hatch: STATERIGHT_TPU_CLOSURE_ESTIMATE=off)"
                    + _orl_hint(self._states[big][-1])
                )

        # seed from the real initial system state
        (init,) = m.init_states()
        self._init_state = init
        for i, s in enumerate(init.actor_states):
            code, ok = add_state(i, s)
            if not ok:
                raise CompileError(f"init state of actor {i} violates bound")
        for env in init.network.iter_deliverable():
            _, ok = add_env(env)
            if not ok:
                raise CompileError(f"init envelope {env!r} violates bound")

        def process(i: int, s_code: int, e_code: int) -> None:
            if (i, s_code, e_code) in trans:
                # Every pair is queued from both sides (new-state x known
                # envelopes and new-envelope x known states); run the real
                # handler only once.
                return
            env = self._envs[e_code]
            s = self._states[i][s_code]
            out = Out()
            try:
                ret = m.actors[i].on_msg(Id(i), s, env.src, env.msg, out)
            except CompileError:
                raise
            except Exception:
                # The closure pairs every known state with every known
                # envelope; protocol invariants can make some pairs
                # impossible, and handlers may crash on them.  Treat the
                # transition as poison: if it were actually reachable the
                # object model would crash identically, and a device run
                # that ever takes it produces a loudly-failing poisoned row
                # instead of a silent divergence.
                trans[(i, s_code, e_code)] = (s_code, (), True, -1)
                return
            if ret is None and not out.commands:
                trans[(i, s_code, e_code)] = (-1, (), False, -1)
                return
            new_s = s if ret is None else ret
            poison = False
            new_code, ok = add_state(i, new_s)
            if not ok:
                # Bound-crossing successor: keep the transition VALID as a
                # poisoned self-loop so a too-tight state_bound surfaces as a
                # loudly-failing poisoned row on device, never as a silently
                # pruned reachable transition.
                new_code, poison = s_code, True
            sends, teff, poison = self._effects(i, out, add_env, poison)
            trans[(i, s_code, e_code)] = (new_code, sends, poison, teff)

        def process_timeout(i: int, s_code: int) -> None:
            if (i, s_code) in ttrans:
                return
            s = self._states[i][s_code]
            out = Out()
            try:
                ret = m.actors[i].on_timeout(Id(i), s, out)
            except CompileError:
                raise
            except Exception:
                ttrans[(i, s_code)] = (s_code, (), True, 0)
                return
            new_s = s if ret is None else ret
            poison = False
            new_code, ok = add_state(i, new_s)
            if not ok:
                new_code, poison = s_code, True
            sends, teff, poison = self._effects(i, out, add_env, poison)
            # flag cleared first; only an explicit SetTimer re-arms
            ttrans[(i, s_code)] = (new_code, sends, poison, max(teff, 0))

        while work:
            item = work.popleft()
            if item[0] == "s":
                _, i, s_code = item
                process_timeout(i, s_code)
                calls += 1
                for e_code, env in enumerate(self._envs):
                    if int(env.dst) == i:
                        process(i, s_code, e_code)
                        calls += 1
            else:
                _, e_code = item
                i = int(self._envs[e_code].dst)
                if i < n:
                    for s_code in range(len(self._states[i])):
                        process(i, s_code, e_code)
                        calls += 1
            if est_on and calls >= next_check:
                next_check = calls + _CHECK_EVERY
                _estimate_check()

        # timers exist iff a timer can ever be SET: then (and only then)
        # the encoding carries timer bits and step_rows emits Timeout
        # actions — register workloads compile exactly as before
        self._has_timers = any(init.is_timer_set) or any(
            t[3] == 1 for t in trans.values()
        ) or any(t[3] == 1 for t in ttrans.values())

        # -- freeze tables ---------------------------------------------------
        ne = len(self._envs)
        # A system may send no messages at all (empty envelope universe):
        # allocate a sentinel env column so device gathers stay in range —
        # no slot is ever occupied, so the sentinel values are always
        # masked out.  Stored on self so step_rows' flat-index stride and
        # the table shapes stay in lockstep by construction.
        nep = self._ne_padded = max(ne, 1)
        self.K = max(
            (len(snds) for (_, snds, _, _) in trans.values()), default=0
        )
        self.Kt = max(
            (len(snds) for (_, snds, _, _) in ttrans.values()), default=0
        )
        self._trans_np = []
        self._sends_np = []
        self._poison_np = []
        self._teff_np = []
        for i in range(n):
            ns = len(self._states[i])
            ti = np.full((ns, nep), -1, np.int32)
            pi = np.zeros((ns, nep), bool)
            ki = np.full((ns, nep, max(self.K, 1)), -1, np.int32)
            ei = np.full((ns, nep), -1, np.int32)
            for (ai, sc, ec), (nc, snds, poison, teff) in trans.items():
                if ai != i:
                    continue
                ti[sc, ec] = nc
                pi[sc, ec] = poison
                ei[sc, ec] = teff
                for k, s in enumerate(snds):
                    ki[sc, ec, k] = s
            self._trans_np.append(ti)
            self._sends_np.append(ki)
            self._poison_np.append(pi)
            self._teff_np.append(ei)
        # timeout tables: (i, s) -> successor code / sends / poison / new bit
        self._ttrans_np = []
        self._tsends_np = []
        self._tpoison_np = []
        self._tbit_np = []
        for i in range(n):
            ns = len(self._states[i])
            ti = np.arange(ns, dtype=np.int32)  # default: state unchanged
            pi = np.zeros(ns, bool)
            bi = np.zeros(ns, np.int32)
            ki = np.full((ns, max(self.Kt, 1)), -1, np.int32)
            for (ai, sc), (nc, snds, poison, tbit) in ttrans.items():
                if ai != i:
                    continue
                ti[sc] = nc
                pi[sc] = poison
                bi[sc] = tbit
                for k, s in enumerate(snds):
                    ki[sc, k] = s
            self._ttrans_np.append(ti)
            self._tsends_np.append(ki)
            self._tpoison_np.append(pi)
            self._tbit_np.append(bi)

        # per-envelope metadata (padded to the sentinel width like the
        # transition tables above)
        pad = [0] * (nep - ne)
        self._env_dst = np.asarray(
            [int(e.dst) for e in self._envs] + pad, np.int32
        )
        # directed flow id (ordered networks): the envelope code determines
        # (src, dst), so same-code implies same flow
        self._env_pair = np.asarray(
            [int(e.src) * self.n_actors + int(e.dst) for e in self._envs]
            + pad,
            np.int32,
        )
        kinds = np.full(nep, _K_OTHER, np.int32)
        vals = np.zeros(nep, np.int32)
        chosen = np.zeros(nep, bool)
        if not self.general:  # register-workload history/property metadata
            for c, e in enumerate(self._envs):
                if e.msg[0] == "put_ok":
                    kinds[c] = _K_PUT_OK
                elif e.msg[0] == "put_fail":
                    kinds[c] = _K_PUT_FAIL
                elif e.msg[0] == "get_ok":
                    kinds[c] = _K_GET_OK
                    v = e.msg[2]
                    if self._wo and v == NULL_VALUE:
                        v = None
                    vals[c] = self.hist._value_code(v)
                    chosen[c] = e.msg[2] != NULL_VALUE
        self._env_kind = kinds
        self._env_val = vals
        self._env_chosen = chosen
        self._client_of = np.asarray(
            [
                self.clients.index(i) if i in self.clients else -1
                for i in range(n)
            ],
            np.int32,
        )
        self._freeze_records()

    def _freeze_records(self) -> None:
        """Pack what the deliver block reads into two record tables, from
        the per-actor tables above (which stay the host's source of truth):

         - the **envelope record** ``uint32[ne, words]``: everything a bare
           envelope code decides - its destination (``n_actors`` for an id
           that is no actor's), on an ordered network its flow id, for a
           register workload its history kind, value code and the client
           index of its destination (+ 1; 0 = not a client);
         - the **transition record** ``uint32[max_S * ne, words]``, ONE
           table overlaid over the actors and indexed by ``(state code of
           the envelope's destination) * ne + envelope code``: next state
           code + valid bit, poison, the K send codes each with its
           present bit and (ordered) its destination - the send's flow id
           is ``deliverer * n_actors + that`` - and the timer effect + 1
           where the model has timers.

        The overlay is sound because an envelope has one destination:
        actor ``i``'s table has entries only in the columns of envelopes
        addressed to ``i``.  That is checked here, not assumed - two
        actors claiming one entry, or an entry whose actor is not the
        envelope's destination, raise :class:`CompileError`.  The word
        counts are what the universes' bit widths need
        (:class:`RecordLayout`): a wider protocol gets another word."""
        n, nep, K = self.n_actors, self._ne_padded, self.K
        env_dst = self._env_dst.astype(np.int64)
        env_fields = [("dst", n)]
        env_cols = {"dst": np.minimum(env_dst, n)}
        if self.ordered:
            env_fields.append(("pair", int(self._env_pair.max())))
            env_cols["pair"] = self._env_pair
        if self.C:
            ci = np.zeros(nep, np.int64)
            actor = env_dst < n
            ci[actor] = self._client_of[env_dst[actor]] + 1
            env_fields += [
                ("kind", int(self._env_kind.max())),
                ("val", int(self._env_val.max())),
                ("ci", self.C),
            ]
            env_cols.update(kind=self._env_kind, val=self._env_val, ci=ci)
        self._env_layout = RecordLayout(env_fields)
        self._env_rec_np = self._env_layout.pack(**env_cols)

        max_s = max(len(st) for st in self._states)
        fields = [("next", max_s - 1), ("valid", 1), ("poison", 1)]
        for k in range(K):
            fields += [(f"send{k}", nep - 1), (f"has{k}", 1)]
            if self.ordered:
                fields.append((f"sdst{k}", int(env_dst.max())))
        if self._has_timers:
            fields.append(("teff", 2))
        self._trans_layout = RecordLayout(fields)
        cols = {
            name: np.zeros((max_s, nep), np.int64) for name, _ in fields
        }
        owner = np.full((max_s, nep), -1, np.int64)
        env_src = np.asarray(
            [int(e.src) for e in self._envs] + [0] * (nep - len(self._envs)),
            np.int64,
        )
        for i in range(n):
            ti, ki = self._trans_np[i], self._sends_np[i]
            si = ti.shape[0]
            claimed = (
                (ti >= 0)
                | self._poison_np[i]
                | (ki >= 0).any(-1)
                | (self._teff_np[i] >= 0)
            )
            clash = claimed & (owner[:si] >= 0)
            if clash.any():
                sc, ec = (int(x) for x in np.argwhere(clash)[0])
                raise CompileError(
                    f"actors {int(owner[sc, ec])} and {i} both claim the "
                    f"transition of (state code {sc}, envelope code {ec}): "
                    "the per-actor tables do not overlay into one"
                )
            stray = claimed & (env_dst[None, :] != i)
            if stray.any():
                sc, ec = (int(x) for x in np.argwhere(stray)[0])
                raise CompileError(
                    f"actor {i} has a transition on envelope code {ec}, "
                    f"which is addressed to {int(env_dst[ec])}: an entry's "
                    "actor must be its envelope's destination"
                )
            owner[:si][claimed] = i
            cols["next"][:si] += np.where(ti >= 0, ti, 0)
            cols["valid"][:si] += ti >= 0
            cols["poison"][:si] += self._poison_np[i]
            for k in range(K):
                code = ki[:, :, k]
                has = code >= 0
                # a send's source is the deliverer, so its flow id is
                # deliverer * n + destination: what env_pair holds
                assert (env_src[code[has]] == i).all()
                cols[f"send{k}"][:si] += np.where(has, code, 0)
                cols[f"has{k}"][:si] += has
                if self.ordered:
                    cols[f"sdst{k}"][:si] += np.where(
                        has, env_dst[np.maximum(code, 0)], 0
                    )
            if self._has_timers:
                cols["teff"][:si] += self._teff_np[i] + 1  # 0 = keep
        self._trans_rec_np = self._trans_layout.pack(
            **{name: c.reshape(-1) for name, c in cols.items()}
        )

    def _effects(self, i: int, out: Out, add_env, poison: bool):
        """Fold a handler's command list into (send codes, timer effect,
        poison).  Timer commands apply sequentially — the last one wins —
        mirroring ``_process_commands``; ``-1`` means no timer command."""
        sends = []
        teff = -1
        for c in out.commands:
            if isinstance(c, SetTimer):
                teff = 1
            elif isinstance(c, CancelTimer):
                teff = 0
            else:
                assert isinstance(c, Send)
                snd = Envelope(src=Id(i), dst=c.dst, msg=c.msg)
                if (
                    not self.general
                    and snd.msg[0] == "put"
                    and self._put_count == 1
                ):
                    # put_count=1 histories invoke every write at start; a
                    # mid-run put means the workload isn't the declared
                    # script.  Multi-op workloads (put_count >= 2) send
                    # their later puts mid-run by design — the multi-op
                    # codec's phase indices model exactly that.
                    raise CompileError(
                        "a client declaring put_count=1 sent a put mid-run: "
                        "its sends do not match the declared one-write "
                        "script (custom client? declare the real put_count)"
                    )
                sc, ok = add_env(snd)
                poison |= not ok
                sends.append(sc)
        return tuple(sends), teff, poison

    # -- per-channel layout (ROADMAP "Per-channel network encoding") --------

    def _build_channel_layout(self) -> None:
        """Freeze the per-(src,dst)-channel row layout: one slot region
        per directed channel of the envelope universe, capacity = that
        channel's distinct-code count (so the unordered semantics can
        NEVER overflow a region — a region full of distinct codes holds
        every code of its channel), plus the static per-channel metadata
        the channel step kernel keys its python-level structure on:
        which channels can poison (table poisons), which carry
        register-workload return kinds (history writers), which touch
        the recipient's timer, and the per-send-slot target-channel sets
        (what makes a send's writes statically confined)."""
        chans: dict = {}
        for c, e in enumerate(self._envs):
            chans.setdefault(e.channel, []).append(c)
        self._channels = sorted(chans)
        self._ch_codes = [
            np.asarray(chans[k], np.int32) for k in self._channels
        ]
        if self.ordered and self._per_channel_depth:
            # ordered flows hold duplicates at distinct ranks, so a flow
            # can outgrow its code universe (retransmits); the knob buys
            # headroom, bounded by the rank field's width
            self._ch_cap = [
                min(
                    max(len(chans[k]), int(self._per_channel_depth)),
                    COUNT_MASK,
                )
                for k in self._channels
            ]
        else:
            self._ch_cap = [len(chans[k]) for k in self._channels]
        self._ch_base = []
        base = 0
        for cap in self._ch_cap:
            self._ch_base.append(base)
            base += cap
        self._chan_of = np.full(self._ne_padded, -1, np.int32)
        for ci, codes in enumerate(self._ch_codes):
            self._chan_of[codes] = ci
        n = self.n_actors
        self._ch_poison_any = []
        self._ch_ret_kind = []
        self._ch_timer = []
        self._ch_targets = []  # per channel: per send slot k, sorted cis
        for ci, (_s, d) in enumerate(self._channels):
            codes = self._ch_codes[ci]
            if d >= n:  # undeliverable destination: no deliver action
                self._ch_poison_any.append(False)
                self._ch_ret_kind.append(False)
                self._ch_timer.append(False)
                self._ch_targets.append([])
                continue
            self._ch_poison_any.append(
                bool(self._poison_np[d][:, codes].any())
            )
            # history updates apply only when the DESTINATION is a client
            # (the multiset kernel's `ci >= 0` guard): a ret-kind envelope
            # relayed to a server must not touch the history fields
            self._ch_ret_kind.append(
                bool((self._env_kind[codes] != _K_OTHER).any())
                and int(self._client_of[d]) >= 0
            )
            self._ch_timer.append(
                bool((self._teff_np[d][:, codes] != -1).any())
            )
            ks = self._sends_np[d][:, codes, :]
            self._ch_targets.append([
                sorted({
                    int(self._chan_of[c])
                    for c in np.unique(ks[..., k][ks[..., k] >= 0])
                })
                for k in range(max(self.K, 1))
            ])
        if self._has_timers:
            self._t_targets = [
                [
                    sorted({
                        int(self._chan_of[c])
                        for c in np.unique(
                            self._tsends_np[i][:, k][
                                self._tsends_np[i][:, k] >= 0
                            ]
                        )
                    })
                    for k in range(max(self.Kt, 1))
                ]
                for i in range(n)
            ]
        #: channels whose codes include a chosen-capable (non-null get_ok)
        #: envelope — the ONLY regions the per-channel "value chosen"
        #: property reads, which is what keeps internal-channel deliveries
        #: property-invisible for the POR C2 condition
        self._chosen_channels = [
            ci
            for ci, codes in enumerate(self._ch_codes)
            if bool(self._env_chosen[codes].any())
        ]

    def _pack_network(self, pairs) -> tuple:
        """``[(envelope, count_or_rank), ...] -> slot words`` under the
        active layout (the per-channel analogue of ``SlotCodec.pack``:
        sorted per region, EMPTY-padded to each region's capacity)."""
        if not self.per_channel:
            return self.codec.pack(pairs)
        per: list = [[] for _ in self._channels]
        for env, count in pairs:
            if not 1 <= count <= COUNT_MASK:
                raise ValueError(f"count {count} out of range for {env!r}")
            code = self._env_code[env]  # KeyError = outside the universe
            per[int(self._chan_of[code])].append(
                (code << COUNT_BITS) | count
            )
        words: list = []
        for ci, lst in enumerate(per):
            cap = self._ch_cap[ci]
            if len(lst) > cap:
                raise ValueError(
                    f"channel {self._channels[ci]} holds {len(lst)} "
                    f"envelopes, exceeding its region capacity {cap}"
                )
            lst.sort()
            words += lst + [SLOT_EMPTY] * (cap - len(lst))
        return tuple(words)

    def _unpack_network(self, slot_words) -> list:
        """``slot words -> [(envelope, count_or_rank), ...]`` under the
        active layout."""
        if not self.per_channel:
            return self.codec.unpack(slot_words)
        out = []
        for w in slot_words:
            w = int(w)
            if w == SLOT_EMPTY:
                continue
            out.append((self._envs[w >> COUNT_BITS], w & COUNT_MASK))
        return out

    def _tabulate_properties(self) -> None:
        """Freeze each factored property's predicate into per-actor (or
        per-pair) boolean tables over the compiled state universes.  The
        host evaluates the same predicate directly, so agreement is by
        construction.  Register workloads tabulate their factored EXTRAS
        only (``None`` marks the two standard history-driven properties,
        which ``property_masks`` computes from the history fields)."""
        from ..actor.device_props import FactoredPredicate

        self._prop_tables = []
        n = self.n_actors
        for p in self.model.properties():
            f = p.condition
            if not isinstance(f, FactoredPredicate):
                self._prop_tables.append(None)  # standard register property
                continue
            try:
                if f.kind in ("forall", "exists"):
                    tables = [
                        np.asarray(
                            [bool(f.pred(i, s)) for s in self._states[i]],
                            bool,
                        )
                        for i in range(n)
                    ]
                else:
                    tables = {
                        (i, j): np.asarray(
                            [
                                [
                                    bool(f.pred(i, si, j, sj))
                                    for sj in self._states[j]
                                ]
                                for si in self._states[i]
                            ],
                            bool,
                        )
                        for i in range(n)
                        for j in range(i + 1, n)
                    }
            except Exception as e:
                raise CompileError(
                    f"property {p.name!r}: predicate failed on an enumerated "
                    f"state ({type(e).__name__}: {e}); factored predicates "
                    "must be total over each actor's reachable states"
                ) from e
            self._prop_tables.append((f.kind, tables))

    def _tabulate_boundary(self) -> None:
        """Freeze a factored ``within_boundary`` into per-actor tables; the
        engines' successor mask then mirrors the host checkers' boundary
        filter exactly."""
        if self._boundary is None:
            self._boundary_np = None
            return
        f = self._boundary
        try:
            self._boundary_np = [
                np.asarray(
                    [bool(f.pred(i, s)) for s in self._states[i]], bool
                )
                for i in range(self.n_actors)
            ]
        except Exception as e:
            raise CompileError(
                f"within_boundary predicate failed on an enumerated state "
                f"({type(e).__name__}: {e})"
            ) from e
        if not f(self.model, self._init_state):
            raise CompileError(
                "the initial state is outside within_boundary: the host "
                "checkers would explore nothing; fix the boundary"
            )

    # -- mechanical device symmetry (general fragment) -----------------------

    _SYM_MAX_PERMS = 720  # n! cap: tables are [n!, |universe|]

    def __getattr__(self, name):
        # ``representative_rows``/``representative_key`` appear on demand:
        # the engines probe them with hasattr only when .symmetry() was
        # requested, which is when the permutation tables are first built.
        # (``__getattr__`` fires only after normal lookup fails, so once
        # built the instance attributes take over.)
        if name in ("representative_rows", "representative_key"):
            d = self.__dict__
            if (
                not d.get("_sym_attempted", True)
                and d.get("_sym_tables") is None
                and d.get("general")
            ):
                self._sym_attempted = True
                self._try_build_symmetry()
            if name in self.__dict__:
                return self.__dict__[name]
        raise AttributeError(name)

    def _try_build_symmetry(self) -> None:
        """Mechanical symmetry reduction for compiled models whose actors
        share ONE state universe (fully interchangeable actors, e.g. Raft
        servers).  Mirrors the host ``ActorModelState.representative``
        exactly: the permutation is the stable sort of per-actor state
        ``stable_hash`` keys, and states/envelopes are rewritten through
        the real ``rewrite_value`` — tabulated per permutation, so the
        device canonicalizes a whole wavefront with gathers.  The
        canonical output is a *virtual* row (universe codes + permuted
        timer word + remapped slots) used only for hashing; rewritten
        values outside the reachable universe are interned for coding.
        On success the instance gains ``representative_rows`` (device) and
        ``representative_key`` (host), and ``.symmetry()`` works on the
        device engines with zero user code."""
        import math
        from itertools import permutations

        from ..fingerprint import stable_hash
        from ..symmetry import RewritePlan, rewrite_value

        n = self.n_actors
        if n < 2 or math.factorial(n) > self._SYM_MAX_PERMS:
            return
        # the UNION of per-actor universes: symmetric systems reach
        # per-actor value sets that are permuted images of each other, so
        # canonical codes live in the union (virtual rows are never
        # decoded, only hashed)
        universe: list = []
        ucode: dict = {}

        def intern(v) -> int:
            c = ucode.get(v)
            if c is None:
                c = len(universe)
                universe.append(v)
                ucode[v] = c
            return c

        for i in range(n):
            for s in self._states[i]:
                intern(s)
        real_u = len(universe)

        umaps = [
            np.asarray([ucode[s] for s in self._states[i]], np.int32)
            for i in range(n)
        ]
        perms = list(permutations(range(n)))  # lexicographic mapping order
        rw = np.zeros((len(perms), real_u), np.int32)
        # same padded env width as the transition tables (_ne_padded), so
        # a padding-policy change cannot desync the symmetry gathers
        ev = np.zeros((len(perms), self._ne_padded), np.int32)
        env_intern: dict = dict(self._env_code)

        def env_code_of(e: Envelope) -> int:
            c = env_intern.get(e)
            if c is None:
                c = len(env_intern)
                env_intern[e] = c
            return c

        try:
            for pi, mapping in enumerate(perms):
                plan = RewritePlan(list(mapping))
                for u in range(real_u):
                    rw[pi, u] = intern(rewrite_value(universe[u], plan))
                for ec, e in enumerate(self._envs):
                    ev[pi, ec] = env_code_of(
                        Envelope(
                            src=plan.rewrite_id(e.src),
                            dst=plan.rewrite_id(e.dst),
                            msg=rewrite_value(e.msg, plan),
                        )
                    )
        except Exception:
            return  # a state/msg resists rewriting: no mechanical symmetry
        self._sym_tables = {
            "umaps": umaps,
            "keys": np.asarray(
                [np.uint64(stable_hash(v)) for v in universe[:real_u]],
                np.uint64,
            ),
            "rw": rw,
            "ev": ev,
            "fact": [math.factorial(n - 1 - k) for k in range(n)],
        }
        self.representative_rows = self._representative_rows_impl
        self.representative_key = self._representative_key_impl

    def _sym_consts(self):
        import jax.numpy as jnp

        c = self.__dict__.get("_sym_dev")
        if c is None:
            t = self._sym_tables
            c = {
                "umaps": [jnp.asarray(u) for u in t["umaps"]],
                "keys": jnp.asarray(t["keys"]),
                "rw": jnp.asarray(t["rw"]),
                "ev": jnp.asarray(t["ev"]),
            }
            self._sym_dev = c
        return c

    def _representative_rows_impl(self, rows):
        """Canonical VIRTUAL rows (for hashing only): ``[..., n + 1 + NS]``
        u64 — universe codes of the plan-rewritten sorted actor states,
        the permuted timer word, and the envelope-remapped sorted slots.
        Accepts any leading shape (engines pass ``[B, A, W]``)."""
        import jax.numpy as jnp

        cst = self._sym_consts()
        i32, u64 = jnp.int32, jnp.uint64
        pk = self.pk
        n = self.n_actors
        fact = self._sym_tables["fact"]
        ucols = [
            cst["umaps"][i][pk.get(rows, f"a{i}").astype(i32)] for i in range(n)
        ]
        keys = cst["keys"][jnp.stack(ucols, axis=-1)]  # [..., n]
        # old -> new (plan.mapping): the stable sort's rank, from compares
        mapping = stable_rank([keys[..., i] for i in range(n)])
        # lexicographic rank of the mapping tuple = table permutation index
        lead = ucols[0].shape
        perm_id = jnp.zeros(lead, i32)
        for k in range(n):
            c = jnp.zeros(lead, i32)
            for j in range(k + 1, n):
                c = c + (mapping[j] < mapping[k]).astype(i32)
            perm_id = perm_id + c * jnp.int32(fact[k])

        usorted = jnp.stack(place_by_rank(ucols, mapping), axis=-1)  # [..., n]
        codes2 = cst["rw"][perm_id[..., None], usorted]  # [..., n]

        if self._has_timers:
            tb = pk.get(rows, "timers").astype(i32)  # [...]
            tword = pack_by_rank([(tb >> i) & 1 for i in range(n)], mapping, 1)
        else:
            tword = jnp.zeros(lead, i32)

        slots = rows[..., self.pw :]
        occ = slots != u64(SLOT_EMPTY)
        e = jnp.where(occ, (slots >> u64(COUNT_BITS)).astype(i32), 0)
        cnt = slots & u64(COUNT_MASK)
        e2 = cst["ev"][perm_id[..., None], e]
        slot2 = jnp.where(
            occ,
            (e2.astype(u64) << u64(COUNT_BITS)) | cnt,
            u64(SLOT_EMPTY),
        )
        slot2 = slot_canonicalize(slot2)
        return jnp.concatenate(
            [
                codes2.astype(u64),
                tword[..., None].astype(u64),
                slot2,
            ],
            axis=-1,
        )

    def _representative_key_impl(self, state: ActorModelState) -> int:
        """Host-side symmetry key: the fingerprint the device stores for
        ``state``'s class (used by trace reconstruction to match steps)."""
        import numpy as np_

        from ..ops import row_hash

        row = np_.asarray([self.encode_state(state)], np_.uint64)
        return int(np_.asarray(row_hash(self._representative_rows_impl(row)))[0])

    # -- host bridge ---------------------------------------------------------

    def encode_state(self, st: ActorModelState) -> tuple:
        vals: dict[str, int] = {}
        for i, s in enumerate(st.actor_states):
            code = self._state_code[i].get(s)
            if code is None:
                raise RuntimeError(
                    f"actor {i} state {s!r} is outside the compiled universe "
                    "(state_bound too tight, or a closure gap)"
                )
            vals[f"a{i}"] = code
        if self._multi:
            for c, (phase, snaps, rval) in enumerate(
                self.hist.fields_of_tester(st.history)
            ):
                vals[f"h{c}_phase"] = phase
                for m in range(self.hist.K):
                    vals[f"h{c}_snap{m}"] = snaps[m]
                vals[f"h{c}_rval"] = rval
        elif not self.general:
            for c, (phase, snap, rval, wfail) in enumerate(
                self.hist.fields_of_tester(st.history)
            ):
                vals[f"h{c}_phase"] = phase
                vals[f"h{c}_snap"] = snap
                vals[f"h{c}_rval"] = rval
                if self.hist.wfail_bits:
                    vals[f"h{c}_wfail"] = wfail
        if self._has_timers:
            vals["timers"] = sum(
                1 << i for i, t in enumerate(st.is_timer_set) if t
            )
        vals["poison"] = 0
        if self.ordered:
            # slot "count" = 1-based rank within the directed flow (1 = head)
            pairs = (
                (Envelope(k[0], k[1], msg), pos + 1)
                for k, flow in st.network._flows.items()
                for pos, msg in enumerate(flow)
            )
        elif self.dup:
            pairs = ((env, 1) for env in st.network.iter_all())
        else:
            pairs = st.network._counts.items()
        return self.pk.pack(**vals) + self._pack_network(pairs)

    def decode_state(self, row) -> ActorModelState:
        d = self.pk.unpack(row[: self.pw])
        if d["poison"]:
            raise RuntimeError(
                "poisoned row: a transition crossed the compile-time bound "
                "(state_bound/env_bound too tight for this configuration)"
            )
        actors = tuple(
            self._states[i][d[f"a{i}"]] for i in range(self.n_actors)
        )
        if self.general:
            tester = None
        elif self._multi:
            tester = self.hist.tester_of_fields(
                [
                    (
                        d[f"h{c}_phase"],
                        tuple(
                            d[f"h{c}_snap{m}"] for m in range(self.hist.K)
                        ),
                        d[f"h{c}_rval"],
                    )
                    for c in range(self.C)
                ]
            )
        else:
            tester = self.hist.tester_of_fields(
                [
                    (
                        d[f"h{c}_phase"],
                        d[f"h{c}_snap"],
                        d[f"h{c}_rval"],
                        d.get(f"h{c}_wfail", 0)
                        if self.hist.wfail_bits
                        else 0,
                    )
                    for c in range(self.C)
                ]
            )
        timers = (
            tuple(
                bool((d["timers"] >> i) & 1) for i in range(self.n_actors)
            )
            if self._has_timers
            else (False,) * self.n_actors
        )
        pairs = self._unpack_network(row[self.pw :])
        if self.ordered:
            flows: dict = {}
            for env, rank1 in pairs:
                flows.setdefault((env.src, env.dst), []).append(
                    (rank1, env.msg)
                )
            network = OrderedNetwork(
                {
                    k: tuple(
                        msg for _, msg in sorted(v, key=lambda t: t[0])
                    )
                    for k, v in flows.items()
                }
            )
        elif self.dup:
            network = UnorderedDuplicatingNetwork(
                {env: None for env, _ in pairs}
            )
        else:
            network = UnorderedNonDuplicatingNetwork(dict(pairs))
        return ActorModelState(
            actor_states=actors,
            network=network,
            is_timer_set=timers,
            history=tester,
        )

    def init_rows(self) -> np.ndarray:
        # The engines call init_rows() host-side while BUILDING a run, so
        # this is the last guaranteed outside-any-trace moment: populate the
        # device-constant cache here.  A lazy first touch from inside a
        # traced step would memoize trace-local tracers, and any later trace
        # of a different engine build (e.g. after a growth event) would read
        # another trace's tracer — UnexpectedTracerError.  Host-only users
        # (CPU checkers fingerprinting via the twin) never call init_rows
        # and stay numpy-only.
        self._consts()
        if self._sym_tables is not None:
            self._sym_consts()  # same outside-any-trace rule as _consts
        return np.asarray([self.encode_state(self._init_state)], np.uint64)

    # -- device --------------------------------------------------------------

    def _consts_np(self) -> dict:
        """What the step and property programs hold on the device, as the
        numpy it is uploaded from: the two record tables of
        :meth:`_freeze_records` for the deliver block (no per-actor
        ``trans`` / ``sends`` / ``poison`` / ``teff`` and no ``env_*``
        column but ``env_chosen``, which ``sr.props`` reads), the
        per-actor Timeout tables where the model has timers (``[B]``-lane
        look-ups; ``env_pair`` with them where a Timeout's send needs its
        flow id), the boundary and property tables."""
        cst = {
            "env_rec": self._env_rec_np,
            "trans_rec": self._trans_rec_np,
            "env_chosen": self._env_chosen,
        }
        if self._has_timers:
            cst.update(
                ttrans=self._ttrans_np,
                tsends=self._tsends_np,
                tpoison=self._tpoison_np,
                tbit=self._tbit_np,
            )
            if self.ordered and self.Kt and not self.per_channel:
                cst["env_pair"] = self._env_pair
        if self._boundary_np is not None:
            cst["boundary"] = self._boundary_np
        if self.per_channel:
            cst["chan_of"] = self._chan_of
        cst["props"] = list(self._prop_tables)
        return cst

    def _consts(self):
        import jax
        import jax.numpy as jnp

        if self._device_consts is None:
            self._device_consts = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x,
                self._consts_np(),
            )
        return self._device_consts

    def row_domain(self):
        """Declared value bounds for the static sanitizer
        (``stateright_tpu/analysis/``, ``docs/analysis.md``).

        The compiled row's fields are bound by their actual UNIVERSES, not
        their bit widths: ``a{i}`` holds a state code ``< len(states[i])``
        (a 3-bit field over 5 codes proves ``< 5``), and each network slot
        word is either ``EMPTY`` or ``code << COUNT_BITS | count`` with
        ``code < len(envs)`` — which is exactly what lets the interval
        pass prove the deliver block's one table gather in range instead
        of reporting the whole kernel undecidable: the overlaid
        transition record is indexed by ``sc_dst * ne + ecode``, and
        ``sc_dst`` (:meth:`_state_of`: stand-alone selects over the
        ``a{i}`` fields joined by ``max``) is bounded by the UNION of
        these bounds, ``max_i len(states[i]) - 1`` - below the record's
        ``max_i len(states[i]) * ne`` rows."""
        from .tensor_model import RowDomain

        bounds = {
            f"a{i}": max(0, len(self._states[i]) - 1)
            for i in range(self.n_actors)
        }
        dom = RowDomain.from_packer(self.pk, field_bounds=bounds,
                                    width=self.width)
        if self.per_channel:
            # per-region bounds: each channel's words hold only ITS codes,
            # so the slot-word ceiling is the channel's max code — tighter
            # than the global-universe bound of the slot-multiset layout
            for ci, codes in enumerate(self._ch_codes):
                hi = (int(codes.max()) << COUNT_BITS) | COUNT_MASK
                base = self.pw + self._ch_base[ci]
                for w in range(base, base + self._ch_cap[ci]):
                    dom.declare_word(w, hi, may_empty=True)
            return dom
        max_code = max(0, len(self._envs) - 1)
        slot_hi = (max_code << COUNT_BITS) | COUNT_MASK
        for w in range(self.pw, self.width):
            dom.declare_word(w, slot_hi, may_empty=True)
        return dom

    def _env_words(self, cst, ecode):
        """The envelope record of every lane of ``ecode`` (in range by
        construction), as its words: a compare-and-max over the envelope
        universe laid along a MAJOR axis - ``max_e where(ecode == e,
        rec[e], 0)``, one term non-zero - which fuses into vector work,
        where an element gather is a serial fetch a lane whatever it reads
        (alone on a v5e, ns a lane with the timing loop's own 0.07-0.15, at
        32 / 106 / 272 / 1,024 / 4,096 envelopes: 0.26 / 0.41 / 0.45 / 2.1
        / 7.9 against the gather's 0.47 / 8.4 / 8.5 / 5.8 / 7.6; a sum in
        place of the max is a third cheaper and along the MINOR axis three
        times dearer at 272, a chain of 272 constant selects compiles for
        9 s: PERF.md section 6, PR 44).  ``max``, not ``sum``: the interval
        pass keeps the table's own bounds through ``reduce_max``, and the
        0.15 ns a lane between them is 0.02 s of a 110 M-lane check.  Its
        work grows with the universe, so past ``_ENV_SELECT_MAX``
        envelopes the record is gathered instead."""
        import jax.numpy as jnp

        tab = cst["env_rec"]  # uint32[ne, words]
        nep, words = tab.shape
        if self._env_gathered:
            got = tab[ecode]
            return [got[..., w] for w in range(words)]
        lead = (nep,) + (1,) * ecode.ndim
        hit = ecode[None] == jnp.arange(nep, dtype=jnp.int32).reshape(lead)
        return [
            jnp.max(
                jnp.where(hit, tab[:, w].reshape(lead), jnp.uint32(0)),
                axis=0,
            )
            for w in range(words)
        ]

    def _state_of(self, rows, actor):
        """The state code of ``actor[b, ...]`` in row ``b`` (0 for
        ``n_actors``, the code of an id that is no actor's): one select
        an actor over the packed ``a{i}`` fields, joined by ``max`` -
        vector work, no gather.  Not a nested chain
        (``tensor_model.select_along_axis``) on purpose: each select
        stands alone, so the interval pass bounds the result by the UNION
        of the fields' declared bounds (``docs/analysis.md``), which is
        what proves the overlaid record's index in range."""
        import jax
        import jax.numpy as jnp

        out = None
        for i in range(self.n_actors):
            code = self.pk.get(rows, f"a{i}").astype(jnp.int32)
            code = code.reshape(code.shape + (1,) * (actor.ndim - 1))
            term = jnp.where(actor == i, code, 0)
            out = term if out is None else jax.lax.max(out, term)
        return out

    def _trans_words(self, cst, index):
        """The transition record at ``index`` (= destination's state code
        * ne + envelope code), as its words: one row gather."""
        got = cst["trans_rec"][index]
        return [got[..., w] for w in range(got.shape[-1])]

    @property
    def _env_gathered(self) -> bool:
        """Whether :meth:`_env_words` gathers (a universe too large for
        the compare-and-max); read at trace time, like the step."""
        return self._ne_padded > _ENV_SELECT_MAX

    def _step_gathers(self) -> int:
        """Record look-ups the step traces as gather equations at slot
        lanes: one deliver block's in the slot-multiset kernel, every
        deliver channel's in the per-channel one."""
        env = int(self._env_gathered)
        if not self.per_channel:
            return 1 + env
        return sum(
            1 + env * bool(self._ch_ret_kind[ci] and self.C)
            for ci, (_s, d) in enumerate(self._channels)
            if d < self.n_actors
        )

    def step_rows(self, rows):
        if self.per_channel:
            return self._step_rows_per_channel(rows)
        return self._step_rows_multiset(rows)

    @property
    def has_coalesced_step(self) -> bool:
        """Both compiled-twin kernels now have a real coalesced form —
        the per-channel kernel since the expand-coalescing round, the
        slot-multiset kernel since its packed-word write-backs were
        threaded through the same :class:`FieldWriter` seam.
        ``ops/mxu.has_coalesced_step`` consults this so the engines and
        the ledger's landed-recast bookkeeping agree on what the flag
        actually moves."""
        return True

    def step_rows_coalesced(self, rows):
        """Expand-scatter-coalesced step (``ops/mxu.py``,
        docs/roofline.md): the same kernel with each action piece's
        packed-field write-backs assembled as ONE word-stacked block
        (``FieldWriter`` coalesced mode) instead of one full-block slice
        read + scatter per field — both the per-channel kernel and the
        slot-multiset kernel (whose history/timer/poison updates were
        the remaining per-field scatter sites).  Successors/validity
        bit-identical to :meth:`step_rows` (whole-space parity pinned in
        tests)."""
        if self.per_channel:
            return self._step_rows_per_channel(rows, coalesce=True)
        return self._step_rows_multiset(rows, coalesce=True)

    def _step_rows_multiset(self, rows, coalesce=False):
        import jax
        import jax.numpy as jnp

        cst = self._consts()
        i32, u64 = jnp.int32, jnp.uint64
        B = rows.shape[0]
        NS, A, W = self.n_slots, self.max_actions, self.width
        # table env stride (padded: empty envelope universes carry a
        # sentinel column; set where the tables are frozen, in _closure)
        ne = self._ne_padded
        pk = self.pk

        slots = rows[:, self.pw :]  # [B, NS]
        occupied = slots != u64(SLOT_EMPTY)
        el, tl = self._env_layout, self._trans_layout
        with jax.named_scope(TWIN_TABLE):
            ecode = jnp.where(
                occupied, (slots >> u64(COUNT_BITS)).astype(i32), 0
            )  # [B, NS]
            # ONE look-up by envelope code ...
            env = self._env_words(cst, ecode)
            dst = el.get(env, "dst")  # [B, NS]; n_actors = no actor's
            pair = None  # flow id of each slot: ordered networks only
            if self.ordered:
                # count bits hold the 1-based rank within the directed flow;
                # only the head (rank 1) of each flow is deliverable
                # (reference ``model.rs:224-227``)
                rank1 = (slots & u64(COUNT_MASK)).astype(i32)  # [B, NS]
                pair = jnp.where(occupied, el.get(env, "pair"), -1)
                at_head = occupied & (rank1 == 1)

            # -- deliver actions (slot a delivers envelope in slot a) -------
            # ... and ONE by (the destination's state, envelope code):
            # every per-actor table is overlaid in one record
            sc_dst = self._state_of(rows, dst)  # [B, NS]
            rec = self._trans_words(cst, sc_dst * ne + ecode)
            new_scode = tl.get(rec, "next")
            valid = occupied & (tl.get(rec, "valid") == 1)
            poison = occupied & (tl.get(rec, "poison") == 1)
            send_codes = [tl.get(rec, f"send{k}") for k in range(self.K)]
            send_on = [tl.get(rec, f"has{k}") == 1 for k in range(self.K)]
            if self.ordered:
                valid = valid & at_head
                # flow id of each send: its source is the deliverer.  With
                # `pair`, all the look-ups slot_send_ordered's carried ids
                # ever need
                send_pairs = [
                    dst * self.n_actors + tl.get(rec, f"sdst{k}")
                    for k in range(self.K)
                ]

        # -- successor slot arrays ------------------------------------------
        with jax.named_scope(TWIN_NET):
            slots_b = jnp.broadcast_to(slots[:, None, :], (B, NS, NS))
            diag = jnp.eye(NS, dtype=bool)[None]
            if self.ordered:
                # delivering the head removes it and advances the rest of its
                # flow by one rank (empty flows vanish with their last slot)
                pair_a = pair[:, :, None]  # flow of the delivered envelope
                pair_s = pair[:, None, :]  # flow of each slot
                same_flow = (pair_a >= 0) & (pair_a == pair_s)
                slots_d = jnp.where(same_flow, slots_b - u64(1), slots_b)
                slots_d = jnp.where(diag, u64(SLOT_EMPTY), slots_d)
                # a delivery changes no slot's code, so the successors'
                # flow ids are the row's own with the delivered slot freed
                pair_d = jnp.where(diag, -1, pair_s)  # [B, NS, NS]
            else:
                if self.dup:
                    # duplicating network: delivery leaves the envelope in
                    # flight (reference ``network.rs:203-205``); only drops
                    # remove it
                    delivered = slots
                else:
                    count = (slots & u64(COUNT_MASK)).astype(i32)
                    delivered = jnp.where(
                        count <= 1, u64(SLOT_EMPTY), slots - u64(1)
                    )  # [B, NS]
                slots_d = jnp.where(diag, delivered[:, :, None], slots_b)
            for k in range(self.K):
                sk = send_codes[k]
                if self.ordered:
                    slots_d, pair_d, of = slot_send_ordered(
                        slots_d, pair_d, sk.astype(u64), send_pairs[k],
                        valid & send_on[k],
                    )
                else:
                    slots_d, of = slot_send(
                        slots_d, sk.astype(u64), valid & send_on[k],
                        set_semantics=self.dup,
                    )
                poison = poison | of
            slots_d = slot_canonicalize(slots_d)

        # -- successor packed words -----------------------------------------
        # every value below reads from `rows`, never from the written
        # block, so the writes thread through one FieldWriter: eager mode
        # traces the per-field pk.set sites op-for-op, coalesced mode
        # assembles them as one word-stacked concatenate (ops/mxu.py)
        fw = FieldWriter(
            pk,
            jnp.broadcast_to(rows[:, None, :], (B, NS, W)),
            coalesce=coalesce,
        )
        for i in range(self.n_actors):
            cur = pk.get(rows, f"a{i}").astype(i32)[:, None]
            v = jnp.where(
                valid & occupied & (dst == i), new_scode, cur
            )
            fw.set(f"a{i}", v.astype(u64))
        if self._has_timers:
            # a deliver's handler may set/cancel the recipient's timer
            timers_cur = pk.get(rows, "timers").astype(i32)  # [B]
            tnew = jnp.broadcast_to(timers_cur[:, None], (B, NS))
            eff = tl.get(rec, "teff")  # [B, NS]: 0 keep, 1 clear, 2 set
            for i in range(self.n_actors):
                mask = valid & occupied & (dst == i)
                tnew = jnp.where(
                    mask & (eff == 2),
                    tnew | (1 << i),
                    jnp.where(mask & (eff == 1), tnew & ~(1 << i), tnew),
                )
            fw.set("timers", tnew.astype(u64))

        # -- history updates -------------------------------------------------
        with jax.named_scope(TWIN_HISTORY):
            if self.C and self._multi:
                # multi-op workload (put_count >= 2): phase = 2*completed +
                # in_flight.  A put_ok return invokes the next op in the same
                # transition (+2); the final get_ok return just completes (+1).
                # The newly-invoked op's snapshot (peers' completed counts) is
                # scattered into the snap field of the op it belongs to —
                # writes 2..K and the read all carry real-time snapshots here,
                # unlike the K=1 layout where only the read's is non-trivial.
                K = self.hist.K
                eb = self.hist.snap_entry_bits
                kind = el.get(env, "kind")  # [B, NS]
                ci = el.get(env, "ci")  # client index + 1; 0 = no client
                is_ret_w = valid & (kind == _K_PUT_OK) & (ci > 0)
                is_ret_r = valid & (kind == _K_GET_OK) & (ci > 0)
                rv = el.get(env, "val")
                phases = jnp.stack(
                    [
                        pk.get(rows, f"h{c}_phase").astype(i32)
                        for c in range(self.C)
                    ],
                    -1,
                )  # [B, C]
                comp = phases >> 1  # completed ops per thread (stored states)
                for c in range(self.C):
                    m_w = is_ret_w & (ci == c + 1)
                    m_r = is_ret_r & (ci == c + 1)
                    cur_ph = pk.get(rows, f"h{c}_phase").astype(i32)[:, None]
                    new_ph = jnp.where(
                        m_w, cur_ph + 2, jnp.where(m_r, cur_ph + 1, cur_ph)
                    )
                    fw.set(f"h{c}_phase", new_ph.astype(u64))
                    cur_comp = cur_ph >> 1  # [B, 1]
                    snap = jnp.zeros((B, NS), i32)
                    for j in range(self.C):
                        if j == c:
                            continue
                        slot = self.hist._snap_slot(c, j)
                        snap = snap | (comp[:, j : j + 1] << (eb * slot))
                    for m in range(K):
                        sel = m_w & (cur_comp == m)
                        cur_snap = pk.get(rows, f"h{c}_snap{m}").astype(i32)[
                            :, None
                        ]
                        fw.set(
                            f"h{c}_snap{m}",
                            jnp.where(sel, snap, cur_snap).astype(u64),
                        )
                    cur_rv = pk.get(rows, f"h{c}_rval").astype(i32)[:, None]
                    fw.set(
                        f"h{c}_rval",
                        jnp.where(m_r, rv, cur_rv).astype(u64),
                    )
            elif self.C:
                kind = el.get(env, "kind")  # [B, NS]
                ci = el.get(env, "ci")  # client index + 1; 0 = no client
                is_ret_w = (
                    valid
                    & ((kind == _K_PUT_OK) | (kind == _K_PUT_FAIL))
                    & (ci > 0)
                )
                is_ret_r = valid & (kind == _K_GET_OK) & (ci > 0)
                rv = el.get(env, "val")
                phases = jnp.stack(
                    [
                        pk.get(rows, f"h{c}_phase").astype(i32)
                        for c in range(self.C)
                    ],
                    -1,
                )  # [B, C]
                # completed-op count per thread, derived from its phase
                comp = jnp.where(
                    phases == PHASE_W_INFLIGHT,
                    0,
                    jnp.where(phases == PHASE_DONE, 2, 1),
                )  # [B, C]
                for c in range(self.C):
                    m_w = is_ret_w & (ci == c + 1)  # write returned + read invoked
                    m_r = is_ret_r & (ci == c + 1)
                    cur_ph = pk.get(rows, f"h{c}_phase").astype(i32)[:, None]
                    new_ph = jnp.where(
                        m_w,
                        PHASE_R_INFLIGHT,
                        jnp.where(m_r, PHASE_DONE, cur_ph),
                    )
                    fw.set(f"h{c}_phase", new_ph.astype(u64))
                    # read-invocation snapshot: other threads' completed counts
                    if self.C > 1:
                        snap = jnp.zeros((B, NS), i32)
                        for j in range(self.C):
                            if j == c:
                                continue
                            slot = self.hist._snap_slot(c, j)
                            snap = snap | (comp[:, j : j + 1] << (2 * slot))
                        cur_snap = pk.get(rows, f"h{c}_snap").astype(i32)[:, None]
                        fw.set(
                            f"h{c}_snap",
                            jnp.where(m_w, snap, cur_snap).astype(u64),
                        )
                    cur_rv = pk.get(rows, f"h{c}_rval").astype(i32)[:, None]
                    fw.set(
                        f"h{c}_rval",
                        jnp.where(m_r, rv, cur_rv).astype(u64),
                    )
                    if self.hist.wfail_bits:
                        m_wf = m_w & (kind == _K_PUT_FAIL)
                        cur_wf = pk.get(rows, f"h{c}_wfail").astype(i32)[:, None]
                        fw.set(
                            f"h{c}_wfail",
                            jnp.where(m_wf, 1, cur_wf).astype(u64),
                        )

        cur_poison = pk.get(rows, "poison").astype(i32)[:, None]
        fw.set(
            "poison",
            jnp.maximum(jnp.where(poison, 1, 0), cur_poison).astype(u64),
        )
        out = fw.done()
        succ = jnp.concatenate([out[:, :, : self.pw], slots_d], axis=-1)

        if not self.model.lossy:
            return self._append_timeouts(
                rows, slots, pair, cst, succ, valid, coalesce=coalesce
            )

        # -- drop actions (lossy networks): consume without delivering ------
        with jax.named_scope(TWIN_DROP):
            if self.ordered:
                # the object model enumerates Drop only over the
                # deliverable envelopes — flow HEADS (``actor/model.py``
                # iter_deliverable) — so an ordered drop's network effect is
                # exactly the deliver effect: remove the head, advance the
                # rest of its flow
                same_flow = (pair[:, :, None] >= 0) & (
                    pair[:, :, None] == pair[:, None, :]
                )
                slots_drop = jnp.where(
                    diag,
                    u64(SLOT_EMPTY),
                    jnp.where(same_flow, slots_b - u64(1), slots_b),
                )
            else:
                # a duplicating network's drop removes the envelope
                # forever (reference ``network.rs:242-244``);
                # non-duplicating drops one copy
                dropped = (
                    jnp.full_like(slots, u64(SLOT_EMPTY))
                    if self.dup
                    else delivered
                )
                slots_drop = jnp.where(diag, dropped[:, :, None], slots_b)
            drop_rows = jnp.concatenate(
                [
                    jnp.broadcast_to(
                        rows[:, None, : self.pw], (B, NS, self.pw)
                    ),
                    slot_canonicalize(slots_drop),
                ],
                axis=-1,
            )
        succ = jnp.concatenate([succ, drop_rows], axis=1)
        droppable = at_head if self.ordered else occupied
        valid = jnp.concatenate([valid, droppable], axis=1)
        return self._append_timeouts(
            rows, slots, pair, cst, succ, valid, coalesce=coalesce
        )

    def _append_timeouts(self, rows, slots, pair, cst, succ, valid,
                         coalesce=False):
        """Append one Timeout action column per actor (reference
        ``model.rs:234-238,288-306``): valid iff the actor's timer bit is
        set; the tabulated ``on_timeout`` effect updates the actor state,
        appends its sends, and rewrites the timer bit (cleared unless the
        handler re-armed it).  ``pair`` is the flow id of each slot of
        ``slots`` on an ordered network (``slot_send_ordered``'s carried
        ids), else None."""
        if not self._has_timers:
            return succ, valid
        import jax
        import jax.numpy as jnp

        i32, u64 = jnp.int32, jnp.uint64
        pk = self.pk
        B = rows.shape[0]
        n = self.n_actors
        NS = self.n_slots
        timers_cur = pk.get(rows, "timers").astype(i32)  # [B]
        col = jnp.arange(n, dtype=i32)[None, :]  # [1, n]
        # same FieldWriter seam as the deliver block: every value reads
        # from `rows`, so eager traces the pk.set sites op-for-op and
        # coalesced assembles one word-stacked block (ops/mxu.py)
        fw_t = FieldWriter(
            pk,
            jnp.broadcast_to(rows[:, None, :], (B, n, self.width)),
            coalesce=coalesce,
        )
        valid_t = ((timers_cur[:, None] >> col) & 1) == 1  # [B, n]
        poison_t = jnp.zeros((B, n), bool)
        tvals = []
        send_cols = []
        for i in range(n):
            sc = pk.get(rows, f"a{i}").astype(i32)  # [B]
            with jax.named_scope(TWIN_TABLE):
                nc = cst["ttrans"][i][sc]
                pi = cst["tpoison"][i][sc]
                nb = cst["tbit"][i][sc]
                send_cols.append(cst["tsends"][i][sc])  # [B, Kt]
            fw_t.set(
                f"a{i}",
                jnp.where(col == i, nc[:, None], sc[:, None]).astype(u64),
            )
            tvals.append((timers_cur & ~(1 << i)) | (nb << i))
            poison_t = poison_t | ((col == i) & pi[:, None])
        fw_t.set("timers", jnp.stack(tvals, 1).astype(u64))
        slots_t = jnp.broadcast_to(slots[:, None, :], (B, n, NS))
        sk_all = jnp.stack(send_cols, axis=1)  # [B, n, Kt]
        if self.ordered:
            pair_t = jnp.broadcast_to(pair[:, None, :], (B, n, NS))
            with jax.named_scope(TWIN_TABLE):
                send_pairs = [
                    cst["env_pair"][sk_all[..., k]] for k in range(self.Kt)
                ]
        for k in range(self.Kt):
            sk = sk_all[..., k]
            if self.ordered:
                slots_t, pair_t, of = slot_send_ordered(
                    slots_t, pair_t, sk.astype(u64), send_pairs[k],
                    valid_t & (sk >= 0),
                )
            else:
                slots_t, of = slot_send(
                    slots_t, sk.astype(u64), valid_t & (sk >= 0),
                    set_semantics=self.dup,
                )
            poison_t = poison_t | of
        cur_poison = pk.get(rows, "poison").astype(i32)[:, None]
        fw_t.set(
            "poison",
            jnp.maximum(
                jnp.where(poison_t, 1, 0), cur_poison
            ).astype(u64),
        )
        out_t = fw_t.done()
        slots_t = slot_canonicalize(slots_t)
        succ_t = jnp.concatenate([out_t[:, :, : self.pw], slots_t], axis=-1)
        return (
            jnp.concatenate([succ, succ_t], axis=1),
            jnp.concatenate([valid, valid_t], axis=1),
        )

    # -- per-channel step kernel --------------------------------------------

    def _region(self, rows, ci: int):
        """Channel ``ci``'s slot region: a static last-axis slice, so the
        footprint pass keeps per-word lane tracking through it."""
        base = self.pw + self._ch_base[ci]
        return rows[..., base : base + self._ch_cap[ci]]

    def _channel_history(self, fw, valid, ecode, c, cst, B, cap):
        """Register-workload history update for ONE client channel (the
        per-channel twin's analogue of the all-clients history loop in
        the multiset kernel): ``c`` is the client index of the channel's
        static destination; masks are [B, cap] over the channel's slots.
        ``fw`` is the piece's :class:`FieldWriter` — eager mode traces
        the exact pre-writer ``pk.get``/``pk.set`` sequence (pinned)."""
        import jax.numpy as jnp

        i32, u64 = jnp.int32, jnp.uint64
        env = self._env_words(cst, ecode)
        kind = self._env_layout.get(env, "kind")  # [B, cap]
        rv = self._env_layout.get(env, "val")
        phases = jnp.stack(
            [
                fw.get(f"h{j}_phase").astype(i32)[:, 0]
                for j in range(self.C)
            ],
            -1,
        )  # [B, C] (the block rows are pre-update copies of the inputs)
        if self._multi:
            K = self.hist.K
            eb = self.hist.snap_entry_bits
            m_w = valid & (kind == _K_PUT_OK)
            m_r = valid & (kind == _K_GET_OK)
            comp = phases >> 1
            cur_ph = fw.get(f"h{c}_phase").astype(i32)
            new_ph = jnp.where(
                m_w, cur_ph + 2, jnp.where(m_r, cur_ph + 1, cur_ph)
            )
            fw.set(f"h{c}_phase", new_ph.astype(u64))
            cur_comp = cur_ph >> 1
            snap = jnp.zeros((B, cap), i32)
            for j in range(self.C):
                if j == c:
                    continue
                slot = self.hist._snap_slot(c, j)
                snap = snap | (comp[:, j : j + 1] << (eb * slot))
            for m in range(K):
                sel = m_w & (cur_comp == m)
                cur_snap = fw.get(f"h{c}_snap{m}").astype(i32)
                fw.set(
                    f"h{c}_snap{m}",
                    jnp.where(sel, snap, cur_snap).astype(u64),
                )
            cur_rv = fw.get(f"h{c}_rval").astype(i32)
            fw.set(f"h{c}_rval", jnp.where(m_r, rv, cur_rv).astype(u64))
            return fw
        m_w = valid & ((kind == _K_PUT_OK) | (kind == _K_PUT_FAIL))
        m_r = valid & (kind == _K_GET_OK)
        comp = jnp.where(
            phases == PHASE_W_INFLIGHT,
            0,
            jnp.where(phases == PHASE_DONE, 2, 1),
        )
        cur_ph = fw.get(f"h{c}_phase").astype(i32)
        new_ph = jnp.where(
            m_w, PHASE_R_INFLIGHT, jnp.where(m_r, PHASE_DONE, cur_ph)
        )
        fw.set(f"h{c}_phase", new_ph.astype(u64))
        if self.C > 1:
            snap = jnp.zeros((B, cap), i32)
            for j in range(self.C):
                if j == c:
                    continue
                slot = self.hist._snap_slot(c, j)
                snap = snap | (comp[:, j : j + 1] << (2 * slot))
            cur_snap = fw.get(f"h{c}_snap").astype(i32)
            fw.set(
                f"h{c}_snap",
                jnp.where(m_w, snap, cur_snap).astype(u64),
            )
        cur_rv = fw.get(f"h{c}_rval").astype(i32)
        fw.set(f"h{c}_rval", jnp.where(m_r, rv, cur_rv).astype(u64))
        if self.hist.wfail_bits:
            m_wf = m_w & (kind == _K_PUT_FAIL)
            cur_wf = fw.get(f"h{c}_wfail").astype(i32)
            fw.set(
                f"h{c}_wfail",
                jnp.where(m_wf, 1, cur_wf).astype(u64),
            )
        return fw

    def _assemble_piece(self, outp, rows, lead, work):
        """One action family's row piece ``[B, lead, W]``: the updated
        packed words plus every slot region — touched regions
        (re-canonicalized members of ``work``) in place, untouched
        regions as pure broadcast copies of the input slice, which is
        exactly what keeps their footprint a no-write."""
        import jax.numpy as jnp

        B = rows.shape[0]
        parts = [outp]
        for t in range(len(self._channels)):
            if t in work:
                parts.append(slot_canonicalize(work[t]))
            else:
                parts.append(jnp.broadcast_to(
                    self._region(rows, t)[:, None, :],
                    (B, lead, self._ch_cap[t]),
                ))
        return jnp.concatenate(parts, axis=-1)

    def _apply_sends(self, work, rows, valid, send_codes, targets, cst,
                     lead):
        """Apply one action family's sends, confined per STATIC target
        channel: ``send_codes`` [B, lead, K]; ``targets[k]`` lists the
        channels send slot ``k`` can reach (from the frozen tables).
        Returns the overflow mask [B, lead] (False where statically
        impossible — duplicating regions sized to their code universe
        can never overflow, so those actions carry no poison write at
        all)."""
        import jax.numpy as jnp

        u64 = jnp.uint64
        B = rows.shape[0]
        overflow = None
        n_k = send_codes.shape[-1]
        for k in range(n_k):
            if k >= len(targets):
                break
            sk = send_codes[..., k]  # [B, lead]
            for t in targets[k]:
                cur = work.get(t)
                if cur is None:
                    cur = jnp.broadcast_to(
                        self._region(rows, t)[:, None, :],
                        (B, lead, self._ch_cap[t]),
                    )
                # a send code unpacked from the record is bounded by its
                # field's mask, not by the universe: clip both ends
                en = valid & (sk >= 0) & (
                    cst["chan_of"][jnp.clip(sk, 0, self._ne_padded - 1)]
                    == t
                )
                if self.ordered:
                    cur, of = region_send_ordered(cur, sk.astype(u64), en)
                else:
                    cur, of = slot_send(
                        cur, sk.astype(u64), en, set_semantics=self.dup
                    )
                work[t] = cur
                if not self.dup:  # set-semantics regions cannot overflow
                    overflow = of if overflow is None else (overflow | of)
        return overflow

    def _step_rows_per_channel(self, rows, coalesce=False):
        """The per-channel twin's step: the successor stack is assembled
        as one action-axis ``concatenate`` of per-channel pieces whose
        writes are statically confined — its own region (consume), the
        recipient's packed fields, and the send-target regions — so the
        footprint pass decomposes it per action and the conflict matrix
        stops being all-dependent (no ``JX302``; docs/analysis.md
        "Per-channel encoding")."""
        import jax
        import jax.numpy as jnp

        cst = self._consts()
        i32, u64 = jnp.int32, jnp.uint64
        B = rows.shape[0]
        ne = self._ne_padded
        pk = self.pk
        n = self.n_actors
        tl = self._trans_layout
        EMPTYW = u64(SLOT_EMPTY)

        pieces, valids = [], []

        packed = rows[:, : self.pw]  # slice FIRST, then expand: the
        # one-step `rows[:, None, :pw]` indexing lowers to a form the
        # footprint pass cannot keep lane-tracked, and every packed-word
        # footprint would collapse to read-everything

        def packed_broadcast(lead):
            return jnp.broadcast_to(packed[:, None, :], (B, lead, self.pw))

        def region_view(ci):
            cap = self._ch_cap[ci]
            reg = self._region(rows, ci)  # [B, cap]
            occ = reg != EMPTYW
            ecode = jnp.where(
                occ,
                (reg >> u64(COUNT_BITS)).astype(i32),
                i32(int(self._ch_codes[ci][0])),
            )
            return cap, reg, occ, ecode

        def consumed(ci, cap, reg, occ):
            """[B, cap(action), cap(word)] region after consuming slot
            ``a`` (one copy / the flow head) — the non-duplicating
            deliver/drop effect; dup deliveries skip this entirely."""
            with jax.named_scope(TWIN_NET):
                reg_b = jnp.broadcast_to(reg[:, None, :], (B, cap, cap))
                diag = jnp.eye(cap, dtype=bool)[None]
                if self.ordered:
                    occ_b = jnp.broadcast_to(
                        occ[:, None, :], (B, cap, cap)
                    )
                    return jnp.where(
                        diag, EMPTYW,
                        jnp.where(occ_b, reg_b - u64(1), reg_b),
                    )
                count = reg & u64(COUNT_MASK)
                gone = jnp.where(count <= u64(1), EMPTYW, reg - u64(1))
                return jnp.where(diag, gone[:, :, None], reg_b)

        # -- deliver actions: one per (channel, slot) -----------------------
        for ci, (_s, d) in enumerate(self._channels):
            if d >= n:
                continue
            with jax.named_scope(TWIN_TABLE):
                cap, reg, occ, ecode = region_view(ci)
                sc = pk.get(rows, f"a{d}").astype(i32)[:, None]  # [B, 1]
                # the destination is the channel's own: the overlaid
                # record's index needs no select here
                rec = self._trans_words(cst, sc * ne + ecode)  # [B, cap]
                nc = tl.get(rec, "next")
                valid = occ & (tl.get(rec, "valid") == 1)
                if self.ordered:
                    valid = valid & (
                        (reg & u64(COUNT_MASK)).astype(i32) == 1
                    )
                poison = None
                if self._ch_poison_any[ci]:
                    poison = occ & (tl.get(rec, "poison") == 1)
                ks = jnp.stack(
                    [
                        jnp.where(
                            tl.get(rec, f"has{k}") == 1,
                            tl.get(rec, f"send{k}"),
                            -1,
                        )
                        for k in range(self.K)
                    ]
                    or [jnp.full((B, cap), -1, i32)],
                    -1,
                )  # [B, cap, max(K, 1)]; -1 = no send

            if self.dup:
                work = {}
            else:
                work = {ci: consumed(ci, cap, reg, occ)}
            of = self._apply_sends(
                work, rows, valid, ks, self._ch_targets[ci], cst, cap
            )
            if of is not None:
                poison = of if poison is None else (poison | of)

            fw = FieldWriter(pk, packed_broadcast(cap),
                             coalesce=coalesce)
            fw.set(f"a{d}", jnp.where(valid, nc, sc).astype(u64))
            if self._ch_ret_kind[ci] and self.C:
                with jax.named_scope(TWIN_HISTORY):
                    self._channel_history(
                        fw, valid, ecode, int(self._client_of[d]), cst,
                        B, cap,
                    )
            if self._has_timers and self._ch_timer[ci]:
                eff = tl.get(rec, "teff")  # [B, cap]: 0 keep, 1 clear, 2 set
                tcur = pk.get(rows, "timers").astype(i32)[:, None]
                bit = (tcur >> d) & 1
                nb = jnp.where(
                    valid & (eff == 2),
                    1,
                    jnp.where(valid & (eff == 1), 0, bit),
                )
                tnew = (tcur & ~(1 << d)) | (nb << d)
                fw.set("timers", tnew.astype(u64))
            if poison is not None:
                fw.or_field("poison", poison)
            pieces.append(self._assemble_piece(fw.done(), rows, cap, work))
            valids.append(valid)

        # -- drop actions (lossy): every channel, network-only effect -------
        if self.model.lossy:
            with jax.named_scope(TWIN_DROP):
                for ci in range(len(self._channels)):
                    cap, reg, occ, _ecode = region_view(ci)
                    if self.dup:
                        # only drops remove from a duplicating network
                        reg_b = jnp.broadcast_to(
                            reg[:, None, :], (B, cap, cap)
                        )
                        dropped = jnp.where(
                            jnp.eye(cap, dtype=bool)[None], EMPTYW, reg_b
                        )
                        droppable = occ
                    else:
                        # a drop's network effect IS the deliver consume
                        dropped = consumed(ci, cap, reg, occ)
                        droppable = occ & (
                            (reg & u64(COUNT_MASK)).astype(i32) == 1
                        ) if self.ordered else occ
                    pieces.append(self._assemble_piece(
                        packed_broadcast(cap), rows, cap, {ci: dropped}
                    ))
                    valids.append(droppable)

        # -- timeout actions: one per actor ---------------------------------
        if self._has_timers:
            tcur_all = pk.get(rows, "timers").astype(i32)  # [B]
            for i in range(n):
                sc = pk.get(rows, f"a{i}").astype(i32)  # [B]
                with jax.named_scope(TWIN_TABLE):
                    nc = cst["ttrans"][i][sc]
                    nb = cst["tbit"][i][sc]
                valid_i = (((tcur_all >> i) & 1) == 1)[:, None]  # [B, 1]
                fw = FieldWriter(pk, packed_broadcast(1),
                                 coalesce=coalesce)
                fw.set(
                    f"a{i}",
                    jnp.where(valid_i, nc[:, None], sc[:, None]).astype(
                        u64
                    ),
                )
                tnew = (tcur_all[:, None] & ~(1 << i)) | (nb[:, None] << i)
                fw.set("timers", tnew.astype(u64))
                work: dict = {}
                ks = cst["tsends"][i][sc][:, None, :]  # [B, 1, Kt]
                of = self._apply_sends(
                    work, rows, valid_i, ks, self._t_targets[i], cst, 1
                )
                poison = None
                if bool(self._tpoison_np[i].any()):
                    poison = valid_i & cst["tpoison"][i][sc][:, None]
                if of is not None:
                    poison = of if poison is None else (poison | of)
                if poison is not None:
                    fw.or_field("poison", poison)
                pieces.append(self._assemble_piece(fw.done(), rows, 1, work))
                valids.append(valid_i)

        if not pieces:  # message-less, timer-less: one never-valid column
            return (
                rows[:, None, :],
                jnp.zeros((B, 1), bool),
            )
        succ = jnp.concatenate(pieces, axis=1)
        valid = jnp.concatenate(valids, axis=-1)
        return succ, valid

    @property
    def has_boundary(self) -> bool:
        return self._boundary_np is not None

    def poison_rows(self, rows):
        """True per row iff a compile-time bound was crossed reaching it —
        the engines turn any poisoned POPPED row into a loud run failure
        (silent wrong counts otherwise: poisoned rows dedup onto their
        self-loop and quietly truncate the space)."""
        import jax.numpy as jnp

        return self.pk.get(rows, "poison").astype(jnp.int32) == 1

    def boundary_rows(self, rows):
        """``within_boundary`` over encoded rows (the device analogue of the
        host checkers' boundary filter; ``step_rows`` itself mirrors the
        UNfiltered ``next_states``, exactly like the object form).  Present
        only when the model declares a factored boundary — the engines
        check for this method and mask out-of-boundary successors."""
        import jax.numpy as jnp

        cst = self._consts()
        i32 = jnp.int32
        per = [
            cst["boundary"][i][
                self.pk.get(rows, f"a{i}").astype(i32)
            ]
            for i in range(self.n_actors)
        ]
        b = per[0]
        for x in per[1:]:
            b = (b & x) if self._boundary.kind == "forall" else (b | x)
        return b

    def property_masks(self, rows):
        import jax
        import jax.numpy as jnp

        cst = self._consts()
        i32, u64 = jnp.int32, jnp.uint64
        pk = self.pk

        def eval_factored(entry):
            import jax.numpy as jnp_

            n = self.n_actors
            codes = [
                pk.get(rows, f"a{i}").astype(i32) for i in range(n)
            ]
            kind, tables = entry
            if kind in ("forall", "exists"):
                per = [tables[i][codes[i]] for i in range(n)]
                v = per[0]
                for x in per[1:]:
                    v = (v & x) if kind == "forall" else (v | x)
                return v
            conj = kind == "forall_pairs"
            v = jnp_.full((rows.shape[0],), conj, bool)
            for i in range(n):
                for j in range(i + 1, n):
                    x = tables[(i, j)][codes[i], codes[j]]
                    v = (v & x) if conj else (v | x)
            return v

        if self.general:
            return jnp.stack(
                [eval_factored(e) for e in cst["props"]], axis=-1
            )

        with jax.named_scope(PROPS_LIN):
            linearizable = self._linearizable_mask(rows)

        if self.per_channel:
            # read ONLY the chosen-capable channels' regions: get_ok
            # envelopes live on statically-known server→client channels,
            # and confining the property's read footprint there is what
            # keeps internal-channel deliveries invisible (the POR C2
            # condition; docs/analysis.md "Per-channel encoding")
            chosen = jnp.zeros((rows.shape[0],), bool)
            for ci in self._chosen_channels:
                reg = self._region(rows, ci)
                r_occ = reg != u64(SLOT_EMPTY)
                r_code = jnp.where(
                    r_occ,
                    (reg >> u64(COUNT_BITS)).astype(i32),
                    i32(int(self._ch_codes[ci][0])),
                )
                chosen = chosen | jnp.any(
                    r_occ & cst["env_chosen"][r_code], axis=-1
                )
        else:
            slots = rows[:, self.pw :]
            occ = slots != u64(SLOT_EMPTY)
            ecode = jnp.where(
                occ, (slots >> u64(COUNT_BITS)).astype(i32), 0
            )
            chosen = jnp.any(occ & cst["env_chosen"][ecode], axis=-1)

        masks = {"linearizable": linearizable, "value chosen": chosen}
        return jnp.stack(
            [
                masks[p.name]
                if cst["props"][k] is None
                else eval_factored(cst["props"][k])
                for k, p in enumerate(self.model.properties())
            ],
            axis=-1,
        )

    def _linearizable_mask(self, rows):
        """The history fields of ``rows`` decoded and held to the codec's
        verdict (closure or table): ``[batch]`` bool."""
        import jax.numpy as jnp

        i32 = jnp.int32
        pk = self.pk
        phases = jnp.stack(
            [pk.get(rows, f"h{c}_phase").astype(i32) for c in range(self.C)],
            -1,
        )
        rvals = jnp.stack(
            [pk.get(rows, f"h{c}_rval").astype(i32) for c in range(self.C)],
            -1,
        )
        if self._multi:
            snaps = jnp.stack(
                [
                    jnp.stack(
                        [
                            pk.get(rows, f"h{c}_snap{m}").astype(i32)
                            for m in range(self.hist.K)
                        ],
                        -1,
                    )
                    for c in range(self.C)
                ],
                -2,
            )  # [B, C, K]
            keys = self.hist.device_key(phases, snaps, rvals)
            return self.hist.device_lookup(keys)
        snaps = jnp.stack(
            [
                pk.get(rows, f"h{c}_snap").astype(i32)
                for c in range(self.C)
            ],
            -1,
        )
        wfails = None
        if self.hist.wfail_bits:
            wfails = jnp.stack(
                [
                    pk.get(rows, f"h{c}_wfail").astype(i32)
                    for c in range(self.C)
                ],
                -1,
            )
        if self.hist.strategy == "closure":
            return self.hist.device_verdict(phases, snaps, rvals)
        keys = self.hist.device_key(phases, snaps, rvals, wfails)
        return self.hist.device_lookup(keys)
