"""Tensor (device) twin of the Paxos register system — the benchmark model.

Encodes the full :class:`~stateright_tpu.actor.model.ActorModelState` of
``paxos_model(C, 3)`` — three server actor states, C register clients, the
in-flight message multiset, and the linearizability-tester history — into
fixed-width ``uint64`` rows, with the complete protocol step (deliver →
handler → sends → history update) as one vectorized jittable kernel
(SURVEY §7.1 "the hard part": actor systems compiled to tensor form).

Design notes:

 - **Network**: sorted-slot multiset (``parallel/actor_tensor.py``); one
   deliver action per occupied slot, matching the object model's
   one-``Deliver``-per-distinct-envelope actions (``actor/model.py``,
   reference ``src/actor/model.rs:214-239``).
 - **Message universe**: every Paxos message is determined by a handful of
   small fields (kind, src, dst, ballot round/leader, and an aux payload:
   a proposal's client index, a ``last_accepted`` code, or a read value).
   Field widths are C-dependent (~21 bits at C ≤ 3, ~26 at C = 7), far
   inside the slot codec's 58-bit envelope budget.  Request ids and values
   are derivable: client ``i``'s put is always ``Put(3+i, chr(65+i))`` and
   its get ``Get(2*(3+i))`` (``actor/register.py``).
 - **History**: with ``put_count=1`` clients, the linearizability tester's
   state is a function of (per-thread phase, read return value, and the
   read-invocation snapshot of peer completion counts).
 - **Linearizable property**: evaluated *on device* by the closure strategy
   (``parallel/history_tensor.py::closure_verdict``): the exhaustive
   interleaving search of the reference
   (``src/semantics/linearizability.rs:178-240``) reduces exactly, for this
   workload, to an acyclicity check on a C×C write-precedence graph —
   O(C³ log C) vectorized boolean ops per state, which is what lets the
   twin scale to the reference's ``paxos check 6`` bench configuration
   (an earlier revision used a (2C)! permutation table, capped at C = 3).
 - **Field widths** are computed from C (ballot rounds ≤ C since each put
   starts exactly one ballot; ``last_accepted`` codes grow with C·rnd), so
   one row layout serves C = 1..7; the C ≤ 7 cap comes from the 3-bit read
   value code and the closure strategy's own cap.
 - **No-op pruning** parity: deliveries whose handler returns None with no
   sends are masked invalid, exactly mirroring the object model's prune
   (reference ``model.rs:253-260``); equality-returning handlers (e.g. a
   duplicate ``Accepted``) still count as transitions.
"""

from __future__ import annotations

import numpy as np

from ..actor import Id
from ..actor.network import Envelope, UnorderedNonDuplicatingNetwork
from ..actor.register import NULL_VALUE
from ..actor.model import ActorModelState
from ..parallel.actor_tensor import (
    COUNT_BITS,
    COUNT_MASK,
    SLOT_EMPTY,
    SlotCodec,
    slot_canonicalize,
    slot_send,
)
from ..parallel.tensor_model import (
    BitPacker,
    FieldWriter,
    TensorModel,
    select_along_axis,
)
from ..semantics.linearizability import LinearizabilityTester
from ..semantics.register import READ, Register, write
from ..telemetry.spans import PROPS_LIN

S = 3  # servers (the benchmark configuration is fixed at 3)

# message kinds
PUT, GET, PUT_OK, GET_OK = 1, 2, 3, 4
PREPARE, PREPARED, ACCEPT, ACCEPTED, DECIDED = 5, 6, 7, 8, 9

MAX_CLIENTS = 7  # 3-bit read-value code + the closure strategy's own cap


class PaxosTensor(TensorModel):
    """Device twin of ``paxos_model(client_count, 3)`` on an unordered
    non-duplicating network (the reference benchmark configuration,
    ``examples/paxos.rs:323-338``).

    A look-up over a per-actor axis of a few entries (a server field or a
    client phase at the envelope's ``dst``) is written as a select over the
    columns (``tensor_model.select_along_axis``), not as an element gather:
    a gather costs ~8 ns a lane on a v5e whatever it gathers from, ten of
    them at ``[batch, actions]`` were 3.07 s of a 6.55 s busy paxos-3 check
    (ledger, PR 30)."""

    #: this hand-tuned twin packs the network as ONE sorted slot multiset
    #: too, so the independence analysis's JX305 escape-hatch pointer
    #: applies: ``PaxosModel.per_channel_()`` routes to the mechanical
    #: compiler's per-channel layout (docs/analysis.md)
    network_encoding = "slot-multiset"

    def __init__(self, model, client_count: int, n_slots: int | None = None):
        if client_count > MAX_CLIENTS:
            raise ValueError(
                f"tensor paxos supports <={MAX_CLIENTS} clients"
            )
        self.model = model
        self.C = C = client_count
        self.n_slots = n_slots if n_slots is not None else max(16, 10 * C)
        self.max_actions = self.n_slots

        # -- C-dependent widths --------------------------------------------
        # Each put starts exactly one ballot (k_put consumes one of the C PUT
        # envelopes on a non-duplicating network), so rounds never exceed C.
        self.max_rnd = max_rnd = max(C, 1)
        la_max = 1 + ((max_rnd - 1) * S + (S - 1)) * C + (C - 1)
        self._aux_b = max(6, la_max.bit_length())
        self._rnd_b = max(3, max_rnd.bit_length())
        self._id_b = max(3, (S + C - 1).bit_length())
        # envelope code bit layout: kind | src | dst | rnd | ldr | aux
        self._ldr_s = self._aux_b
        self._rnd_s = self._ldr_s + 2
        self._dst_s = self._rnd_s + self._rnd_b
        self._src_s = self._dst_s + self._id_b
        self._kind_s = self._src_s + self._id_b
        self._la_max = la_max
        prep_b = (la_max + 1).bit_length()
        prop_b = max(3, (C + 1).bit_length())

        fields = []
        for s in range(S):
            fields += [
                (f"s{s}_rnd", self._rnd_b),
                (f"s{s}_ldr", 2),
                (f"s{s}_prop", prop_b),
                (f"s{s}_prep0", prep_b),
                (f"s{s}_prep1", prep_b),
                (f"s{s}_prep2", prep_b),
                (f"s{s}_acc", 3),
                (f"s{s}_accd", self._aux_b),
                (f"s{s}_dec", 1),
            ]
        for c in range(C):
            fields += [
                (f"c{c}_phase", 2),
                (f"c{c}_rval", 3),
                (f"c{c}_snap", 2 * C),
            ]
        fields += [("hvalid", 1), ("overflow", 1)]
        self.pk = BitPacker(fields)
        self.pw = self.pk.width
        self.width = self.pw + self.n_slots
        self.codec = SlotCodec(self.n_slots, self._encode_env, self._decode_env)

    # ------------------------------------------------------------------
    # host-side: la / proposal / envelope codes
    # ------------------------------------------------------------------

    def _la_code(self, la) -> int:
        """Option<(Ballot, Proposal)> -> ``_aux_b``-bit code; numeric order
        matches the tuple order used by the prepare-quorum ``max``
        (``paxos.py``)."""
        if la is None:
            return 0
        (rnd, ldr), proposal = la
        ci = int(proposal[1]) - S
        code = 1 + ((rnd - 1) * S + int(ldr)) * self.C + ci
        assert 0 < code <= self._la_max, la
        return code

    def _la_decode(self, code: int):
        if code == 0:
            return None
        x = code - 1
        ci = x % self.C
        x //= self.C
        ldr = x % S
        rnd = x // S + 1
        return ((rnd, Id(ldr)), self._proposal(ci))

    def _proposal(self, ci: int) -> tuple:
        return (S + ci, Id(S + ci), chr(ord("A") + ci))

    def _encode_env(self, env: Envelope) -> int:
        kind = src = dst = rnd = ldr = aux = 0
        src, dst = int(env.src), int(env.dst)
        m = env.msg
        if m[0] == "put":
            kind = PUT
        elif m[0] == "get":
            kind = GET
        elif m[0] == "put_ok":
            kind = PUT_OK
        elif m[0] == "get_ok":
            kind, aux = GET_OK, self._value_code(m[2])
        else:  # internal
            im = m[1]
            (rnd, ldr_id) = im[1]
            ldr = int(ldr_id)
            if im[0] == "prepare":
                kind = PREPARE
            elif im[0] == "prepared":
                kind, aux = PREPARED, self._la_code(im[2])
            elif im[0] == "accept":
                kind, aux = ACCEPT, int(im[2][1]) - S
            elif im[0] == "accepted":
                kind = ACCEPTED
            elif im[0] == "decided":
                kind, aux = DECIDED, int(im[2][1]) - S
            else:
                raise ValueError(f"unknown internal message {im!r}")
        assert rnd <= self.max_rnd and aux < (1 << self._aux_b), env
        return (
            (kind << self._kind_s)
            | (src << self._src_s)
            | (dst << self._dst_s)
            | (rnd << self._rnd_s)
            | (ldr << self._ldr_s)
            | aux
        )

    def _decode_env(self, code: int) -> Envelope:
        idm = (1 << self._id_b) - 1
        aux = code & ((1 << self._aux_b) - 1)
        ldr = (code >> self._ldr_s) & 3
        rnd = (code >> self._rnd_s) & ((1 << self._rnd_b) - 1)
        dst = (code >> self._dst_s) & idm
        src = (code >> self._src_s) & idm
        kind = code >> self._kind_s
        ballot = (rnd, Id(ldr))
        if kind == PUT:
            ci = src - S
            msg = ("put", S + ci, chr(ord("A") + ci))
        elif kind == GET:
            msg = ("get", 2 * src)
        elif kind == PUT_OK:
            msg = ("put_ok", dst)
        elif kind == GET_OK:
            msg = ("get_ok", 2 * dst, self._value_decode(aux))
        elif kind == PREPARE:
            msg = ("internal", ("prepare", ballot))
        elif kind == PREPARED:
            msg = ("internal", ("prepared", ballot, self._la_decode(aux)))
        elif kind == ACCEPT:
            msg = ("internal", ("accept", ballot, self._proposal(aux)))
        elif kind == ACCEPTED:
            msg = ("internal", ("accepted", ballot))
        elif kind == DECIDED:
            msg = ("internal", ("decided", ballot, self._proposal(aux)))
        else:
            raise ValueError(f"bad envelope code {code:#x}")
        return Envelope(src=Id(src), dst=Id(dst), msg=msg)

    def _value_code(self, v: str) -> int:
        return 0 if v == NULL_VALUE else ord(v) - ord("A") + 1

    def _value_decode(self, code: int) -> str:
        return NULL_VALUE if code == 0 else chr(ord("A") + code - 1)

    # ------------------------------------------------------------------
    # host-side: state <-> row
    # ------------------------------------------------------------------

    def encode_state(self, st: ActorModelState) -> tuple:
        C = self.C
        vals: dict[str, int] = {}
        for s in range(S):
            a = st.actor_states[s]
            rnd, ldr = a.ballot
            assert rnd <= self.max_rnd, a
            vals[f"s{s}_rnd"] = rnd
            vals[f"s{s}_ldr"] = int(ldr)
            vals[f"s{s}_prop"] = (
                0 if a.proposal is None else int(a.proposal[1]) - S + 1
            )
            prep = dict(a.prepares)
            for j in range(S):
                la = prep.get(Id(j), "absent")
                vals[f"s{s}_prep{j}"] = (
                    0 if la == "absent" else 1 + self._la_code(la)
                )
            vals[f"s{s}_acc"] = sum(1 << int(i) for i in a.accepts)
            vals[f"s{s}_accd"] = self._la_code(a.accepted)
            vals[f"s{s}_dec"] = int(a.is_decided)

        tester: LinearizabilityTester = st.history
        for c in range(C):
            thread = S + c
            cs = st.actor_states[thread]
            completed = tester.history_by_thread.get(thread, ())
            in_flight = tester.in_flight_by_thread.get(thread)
            phase = len(completed)
            assert (phase == 2) == (in_flight is None), (c, tester)
            # client actor state is in lockstep with the tester phase
            expect = {
                0: (thread, 1),
                1: (2 * thread, 2),
                2: (None, 3),
            }[phase]
            assert (cs.awaiting, cs.op_count) == expect, (c, cs, phase)
            vals[f"c{c}_phase"] = phase
            rval = 0
            snap_src = None
            if phase == 2:
                snap_src, op, ret = completed[1]
                assert op == READ and ret[0] == "read_ok", completed
                rval = self._value_code(ret[1])
            elif phase == 1:
                snap_src, op = in_flight
                assert op == READ, in_flight
            if phase >= 1:
                assert completed[0][0] == () and completed[0][1] == write(
                    chr(ord("A") + c)
                ), completed
            snap = 0
            if snap_src is not None:
                for peer, idx in snap_src:
                    t = int(peer) - S
                    assert 0 <= t < C and 0 <= idx <= 1, snap_src
                    snap |= (idx + 1) << (2 * t)
            vals[f"c{c}_rval"] = rval
            vals[f"c{c}_snap"] = snap
        vals["hvalid"] = int(tester.valid)
        vals["overflow"] = 0

        counts = st.network._counts
        return self.pk.pack(**vals) + self.codec.pack(
            (env, cnt) for env, cnt in counts.items()
        )

    def decode_state(self, row) -> ActorModelState:
        from ..models.paxos import PaxosState

        C = self.C
        d = self.pk.unpack(row[: self.pw])
        if d["overflow"]:
            raise RuntimeError(
                "network slot overflow: raise n_slots on PaxosTensor"
            )
        actors = []
        for s in range(S):
            prepares = tuple(
                sorted(
                    (Id(j), self._la_decode(d[f"s{s}_prep{j}"] - 1))
                    for j in range(S)
                    if d[f"s{s}_prep{j}"] > 0
                )
            )
            prop = d[f"s{s}_prop"]
            actors.append(
                PaxosState(
                    ballot=(d[f"s{s}_rnd"], Id(d[f"s{s}_ldr"])),
                    proposal=None if prop == 0 else self._proposal(prop - 1),
                    prepares=prepares,
                    accepts=frozenset(
                        Id(i) for i in range(S) if d[f"s{s}_acc"] & (1 << i)
                    ),
                    accepted=self._la_decode(d[f"s{s}_accd"]),
                    is_decided=bool(d[f"s{s}_dec"]),
                )
            )

        from ..actor.register import RegisterClientState

        history: dict[int, tuple] = {}
        in_flight: dict[int, tuple] = {}
        for c in range(C):
            thread = S + c
            phase = d[f"c{c}_phase"]
            snap = tuple(
                sorted(
                    (S + t, ((d[f"c{c}_snap"] >> (2 * t)) & 3) - 1)
                    for t in range(C)
                    if (d[f"c{c}_snap"] >> (2 * t)) & 3
                )
            )
            w_complete = ((), write(chr(ord("A") + c)), ("write_ok",))
            if phase == 0:
                history[thread] = ()
                in_flight[thread] = ((), write(chr(ord("A") + c)))
                cs = RegisterClientState(awaiting=thread, op_count=1)
            elif phase == 1:
                history[thread] = (w_complete,)
                in_flight[thread] = (snap, READ)
                cs = RegisterClientState(awaiting=2 * thread, op_count=2)
            else:
                rv = self._value_decode(d[f"c{c}_rval"])
                history[thread] = (w_complete, (snap, READ, ("read_ok", rv)))
                cs = RegisterClientState(awaiting=None, op_count=3)
            actors.append(cs)

        tester = LinearizabilityTester(
            Register(NULL_VALUE),
            history,
            in_flight,
            valid=bool(d["hvalid"]),
        )
        network = UnorderedNonDuplicatingNetwork(
            dict(self.codec.unpack(row[self.pw :]))
        )
        return ActorModelState(
            actor_states=tuple(actors),
            network=network,
            is_timer_set=(False,) * (S + C),
            history=tester,
        )

    def init_rows(self) -> np.ndarray:
        return np.asarray(
            [self.encode_state(s) for s in self.model.init_states()],
            np.uint64,
        )

    # ------------------------------------------------------------------
    # device-side
    # ------------------------------------------------------------------

    def step_rows(self, rows):
        return self._step_rows_impl(rows, coalesce=False)

    def step_rows_coalesced(self, rows):
        """Expand-scatter-coalesced step (``ops/mxu.py``, docs/roofline.md):
        the same transition function with the packed-word write-backs
        assembled as ONE word-stacked block (``FieldWriter`` coalesced
        mode) instead of 37 per-field scatters — the JX400 #1 expand hot
        spot on paxos-3.  Successors and validity are bit-identical to
        :meth:`step_rows` (whole-space parity pinned in tests); only the
        assembly shape changes.  Selected by the engines under
        ``CheckerBuilder.mxu()`` / ``--mxu``."""
        return self._step_rows_impl(rows, coalesce=True)

    def _step_rows_impl(self, rows, coalesce):
        import jax.numpy as jnp

        C, NS, pk = self.C, self.n_slots, self.pk
        i32 = jnp.int32
        u64 = jnp.uint64
        B = rows.shape[0]
        A = NS
        W = self.width

        slots = rows[:, self.pw :]  # [B, NS]
        code = slots >> u64(COUNT_BITS)
        count = (slots & u64(COUNT_MASK)).astype(i32)
        occupied = slots != u64(SLOT_EMPTY)

        # envelope fields per slot (= per action)  [B, A]
        idm = u64((1 << self._id_b) - 1)
        aux = (code & u64((1 << self._aux_b) - 1)).astype(i32)
        ldr = ((code >> u64(self._ldr_s)) & u64(3)).astype(i32)
        rnd = (
            (code >> u64(self._rnd_s)) & u64((1 << self._rnd_b) - 1)
        ).astype(i32)
        dst = ((code >> u64(self._dst_s)) & idm).astype(i32)
        src = ((code >> u64(self._src_s)) & idm).astype(i32)
        kind = (code >> u64(self._kind_s)).astype(i32)
        eb = rnd * 4 + ldr  # env ballot, lexicographic key

        def gi(name):  # packed field as [B, 1] int32 (broadcasts over A)
            return pk.get(rows, name).astype(i32)[:, None]

        # server fields stacked [B, S]; then read at dst -> [B, A] (a select
        # over the S columns, not an element gather: class docstring)
        srv = {
            f: jnp.concatenate([gi(f"s{s}_{f}") for s in range(S)], axis=1)
            for f in (
                "rnd", "ldr", "prop", "prep0", "prep1", "prep2",
                "acc", "accd", "dec",
            )
        }
        dstc = jnp.clip(dst, 0, S - 1)

        def at_dst(f):  # [B, A]
            return select_along_axis(srv[f], dstc)

        srnd, sldr = at_dst("rnd"), at_dst("ldr")
        sprop, sacc, saccd, sdec = (
            at_dst("prop"), at_dst("acc"), at_dst("accd"), at_dst("dec"),
        )
        sprep = [at_dst(f"prep{j}") for j in range(S)]
        sb = srnd * 4 + sldr
        is_server = dst < S
        undecided = is_server & (sdec == 0)

        # client fields at dst  [B, A]
        if C > 0:
            cph = jnp.concatenate([gi(f"c{c}_phase") for c in range(C)], axis=1)
            clic = jnp.clip(dst - S, 0, C - 1)
            cphase = select_along_axis(cph, clic)
            # peer phases for the read-invocation snapshot: snap bits over all
            # threads (self slot left 0)
            allph = cph  # [B, C]
        is_client = dst >= S

        def la_code(r, l, ci):
            return 1 + ((r - 1) * S + l) * C + ci

        def ci_of_la(la):
            return (la - 1) % C

        # -- branch masks ---------------------------------------------------
        k_put = (kind == PUT) & undecided & (sprop == 0)
        k_prepare = (kind == PREPARE) & undecided & (sb < eb)
        k_prepared = (kind == PREPARED) & undecided & (eb == sb)
        k_accept = (kind == ACCEPT) & undecided & (sb <= eb)
        k_accepted = (kind == ACCEPTED) & undecided & (eb == sb)
        k_decided = (kind == DECIDED) & undecided
        k_getdec = (kind == GET) & is_server & (sdec == 1)
        k_cputok = (kind == PUT_OK) & is_client & (cphase == 0)
        k_cgetok = (kind == GET_OK) & is_client & (cphase == 1)
        valid = occupied & (
            k_put | k_prepare | k_prepared | k_accept | k_accepted
            | k_decided | k_getdec | k_cputok | k_cgetok
        )

        # -- server successor fields (computed "at dst") --------------------
        ci_src = src - S  # for put: the client index
        put_rnd = srnd + 1

        # prepared bookkeeping
        la_in = aux
        prep_new = [
            jnp.where(
                k_prepared & (src == j),
                1 + la_in,
                jnp.where(k_put, jnp.where(dst == j, 1 + saccd, 0), sprep[j]),
            )
            for j in range(S)
        ]
        prep_count = sum((p > 0).astype(i32) for p in prep_new)
        best_la = (
            jnp.maximum(jnp.maximum(prep_new[0], prep_new[1]), prep_new[2]) - 1
        )
        quorum_p = k_prepared & (prep_count == 2)
        # adopt the most recently accepted proposal from the quorum, else keep
        prop_adopt = jnp.where(best_la > 0, ci_of_la(best_la) + 1, sprop)

        acc_new = jnp.where(
            quorum_p,
            1 << dstc,
            jnp.where(k_put, 0, jnp.where(k_accepted, sacc | (1 << src), sacc)),
        )
        acc_pop = (
            (acc_new & 1) + ((acc_new >> 1) & 1) + ((acc_new >> 2) & 1)
        )
        quorum_a = k_accepted & (acc_pop == 2)

        new_rnd = jnp.where(
            k_put,
            put_rnd,
            jnp.where(k_prepare | k_accept | k_decided, rnd, srnd),
        )
        new_ldr = jnp.where(
            k_put, dstc, jnp.where(k_prepare | k_accept | k_decided, ldr, sldr)
        )
        new_prop = jnp.where(
            k_put, ci_src + 1, jnp.where(quorum_p, prop_adopt, sprop)
        )
        new_accd = jnp.where(
            quorum_p,
            la_code(srnd, sldr, prop_adopt - 1),
            jnp.where(
                k_accept | k_decided, la_code(rnd, ldr, aux), saccd
            ),
        )
        new_dec = jnp.where(quorum_a | k_decided, 1, sdec)

        # -- client successor fields ----------------------------------------
        if C > 0:
            new_phase = jnp.where(
                k_cputok, 1, jnp.where(k_cgetok, 2, cphase)
            )
            new_rval = jnp.where(k_cgetok, aux, 0)
            # snapshot at get-invocation: peer completed counts == phases
            snap_val = jnp.zeros_like(dst)
            for t in range(C):
                peer_phase = jnp.minimum(allph[:, t : t + 1], 2)
                contrib = jnp.where(clic == t, 0, peer_phase) << (2 * t)
                snap_val = snap_val + jnp.where(k_cputok, contrib, 0)

        # -- sends (3 channels) ---------------------------------------------
        def env_code(knd, esrc, edst, ernd, eldr, eaux):
            z = jnp.zeros_like(dst)
            return (
                ((z + knd).astype(u64) << u64(self._kind_s))
                | (esrc.astype(u64) << u64(self._src_s))
                | (edst.astype(u64) << u64(self._dst_s))
                | (ernd.astype(u64) << u64(self._rnd_s))
                | (eldr.astype(u64) << u64(self._ldr_s))
                | eaux.astype(u64)
            )

        z = jnp.zeros_like(dst)
        p1 = jnp.where(dstc + 1 >= S, dstc + 1 - S, dstc + 1)
        p2 = jnp.where(dstc + 2 >= S, dstc + 2 - S, dstc + 2)

        # ch0: single-target sends
        ch0_en = k_prepare | k_accept | quorum_a | k_getdec | k_cputok
        ch0_code = jnp.where(
            k_prepare,
            env_code(PREPARED, dst, src, rnd, ldr, saccd),
            jnp.where(
                k_accept,
                env_code(ACCEPTED, dst, src, rnd, ldr, z),
                jnp.where(
                    quorum_a,
                    env_code(PUT_OK, dst, S + sprop - 1, z, z, z),
                    jnp.where(
                        k_getdec,
                        env_code(
                            GET_OK, dst, src, z, z, ci_of_la(saccd) + 1
                        ),
                        # k_cputok: the follow-up get, to server
                        # (index + op_count) % S with op_count == 1
                        env_code(GET, dst, (dst + 1) % S, z, z, z),
                    ),
                ),
            ),
        )

        # ch1/ch2: peer broadcasts (prepare / accept / decided)
        bcast = k_put | quorum_p | quorum_a
        bc_kind = jnp.where(k_put, PREPARE, jnp.where(quorum_p, ACCEPT, DECIDED))
        bc_rnd = jnp.where(k_put, put_rnd, srnd)
        bc_ldr = jnp.where(k_put, dstc, sldr)
        bc_aux = jnp.where(
            quorum_p, prop_adopt - 1, jnp.where(quorum_a, sprop - 1, z)
        )
        ch1_code = env_code(bc_kind, dst, p1, bc_rnd, bc_ldr, bc_aux)
        ch2_code = env_code(bc_kind, dst, p2, bc_rnd, bc_ldr, bc_aux)

        # -- assemble successor slot arrays ---------------------------------
        slots_b = jnp.broadcast_to(slots[:, None, :], (B, A, NS))
        diag = jnp.eye(A, NS, dtype=bool)[None]  # deliver slot a of action a
        neww = jnp.where(
            count <= 1, u64(SLOT_EMPTY), slots - u64(1)
        )  # [B, A] value for the delivered slot
        slots_d = jnp.where(diag, neww[:, :, None], slots_b)

        of = jnp.zeros((B, A), bool)
        for en, cd in (
            (ch0_en, ch0_code),
            (bcast, ch1_code),
            (bcast, ch2_code),
        ):
            slots_d, o = slot_send(slots_d, cd, en & valid)
            of = of | o
        slots_d = slot_canonicalize(slots_d)

        # -- assemble successor packed words --------------------------------
        # eager: the pre-writer broadcast + per-field pk.set trace,
        # bit-identical (pinned).  Coalesced: the base block covers only
        # the packed words and the writer assembles them as one
        # word-stacked concatenate (FieldWriter; ops/mxu.py).
        if coalesce:
            base = jnp.broadcast_to(
                rows[:, None, : self.pw], (B, A, self.pw)
            )
        else:
            base = jnp.broadcast_to(rows[:, None, :], (B, A, W))
        fw = FieldWriter(pk, base, coalesce=coalesce)

        def scatter_server(name, new_val, old_stacked):
            for s in range(S):
                old = old_stacked[:, s : s + 1]
                v = jnp.where(valid & is_server & (dst == s), new_val, old)
                fw.set(f"s{s}_{name}", v.astype(u64))

        scatter_server("rnd", new_rnd, srv["rnd"])
        scatter_server("ldr", new_ldr, srv["ldr"])
        scatter_server("prop", new_prop, srv["prop"])
        for j in range(S):
            scatter_server(f"prep{j}", prep_new[j], srv[f"prep{j}"])
        scatter_server("acc", acc_new, srv["acc"])
        scatter_server("accd", new_accd, srv["accd"])
        scatter_server("dec", new_dec, srv["dec"])

        for c in range(C):
            m = valid & is_client & (dst == S + c)
            fw.set(
                f"c{c}_phase",
                jnp.where(m, new_phase, cph[:, c : c + 1]).astype(u64),
            )
            fw.set(
                f"c{c}_rval",
                jnp.where(
                    m & k_cgetok, new_rval, gi(f"c{c}_rval")
                ).astype(u64),
            )
            fw.set(
                f"c{c}_snap",
                jnp.where(
                    m & k_cputok, snap_val, gi(f"c{c}_snap")
                ).astype(u64),
            )
        fw.set(
            "overflow",
            jnp.maximum(
                jnp.where(of, 1, 0), gi("overflow")
            ).astype(u64),
        )
        out = fw.done()

        if coalesce:
            succ = jnp.concatenate([out, slots_d], axis=-1)
        else:
            succ = jnp.concatenate(
                [out[:, :, : self.pw], slots_d], axis=-1
            )
        return succ, valid

    def poison_rows(self, rows):
        """True per row iff a send on the way to it found no free network
        slot (``step_rows`` sets the row's ``overflow`` bit and every
        successor keeps it): the message was dropped, so the engines turn
        any such POPPED row into a loud run failure (status ``poison``)
        instead of a quietly smaller space.  ``n_slots`` is the cure."""
        import jax.numpy as jnp

        return self.pk.get(rows, "overflow") == jnp.uint64(1)

    def property_masks(self, rows):
        import jax
        import jax.numpy as jnp

        from ..parallel.history_tensor import closure_verdict

        C, pk = self.C, self.pk
        i32 = jnp.int32
        B = rows.shape[0]

        with jax.named_scope(PROPS_LIN):
            phase = jnp.stack(
                [pk.get(rows, f"c{c}_phase").astype(i32) for c in range(C)], -1
            )  # [B, C]
            rval = jnp.stack(
                [pk.get(rows, f"c{c}_rval").astype(i32) for c in range(C)], -1
            )
            snap = jnp.stack(
                [pk.get(rows, f"c{c}_snap").astype(i32) for c in range(C)], -1
            )
            hvalid = pk.get(rows, "hvalid") == jnp.uint64(1)

            # s[b, i, t] = ops thread t had completed when thread i's read was
            # invoked (the snapshot recorded at get-invocation; self slot 0)
            done = phase == 2
            s = jnp.zeros((B, C, C), i32)
            for i in range(C):
                for t in range(C):
                    if t == i:
                        continue
                    s = s.at[:, i, t].set((snap[:, i] >> (2 * t)) & 3)
            linearizable = closure_verdict(done, s, rval) & hvalid

        # "value chosen": some get_ok with a non-null value is in flight
        slots = rows[:, self.pw :]
        code = slots >> jnp.uint64(COUNT_BITS)
        occ = slots != jnp.uint64(SLOT_EMPTY)
        kind = (code >> jnp.uint64(self._kind_s)).astype(i32)
        aux = (code & jnp.uint64((1 << self._aux_b) - 1)).astype(i32)
        chosen = jnp.any(occ & (kind == GET_OK) & (aux > 0), axis=-1)

        return jnp.stack([linearizable, chosen], axis=-1)
