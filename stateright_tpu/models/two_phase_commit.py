"""Two-phase commit, after the Gray/Lamport TLA+ model "Consensus on
Transaction Commit" (reference ``examples/2pc.rs``).

A transaction manager (TM) coordinates N resource managers (RMs).  The
abstract model tracks each RM's state, the TM's state, which RMs the TM has
seen prepared, and a monotonic message set.  Properties: commit/abort
agreement is reachable (`sometimes`) and no RM ever aborts while another
commits (`always consistent`).

This is also the framework's flagship tensor-form model: :class:`TwoPhaseTensor`
below is the u64-row encoding checked by the TPU wavefront engine; both forms
agree on fingerprints bit-for-bit (``TwoPhaseSys`` is tensor-backed, so even
the CPU checkers fingerprint via the row encoding).

Pinned counts (reference ``examples/2pc.rs:125-140``): 288 @ 3 RMs,
8,832 @ 5 RMs, 665 @ 5 RMs with symmetry reduction.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

from .. import Model, Property
from ..parallel.tensor_model import (
    BitPacker,
    FieldWriter,
    TensorBackedModel,
    TensorModel,
    pack_by_rank,
    stable_rank,
)
from ..symmetry import RewritePlan
from ._cli import (
    apply_encoding,
    apply_perf,
    default_threads,
    make_audit_cmd,
    make_profile_cmd,
    make_capacity_cmd,
    make_compare_cmd,
    make_costmodel_cmd,
    make_report_cmd,
    make_independence_cmd,
    make_sanitize_cmd,
    make_sweep_cmd,
    pop_checked,
    pop_perf,
    pop_supervise_opts,
    pop_watch,
    run_cli,
    run_supervised,
    spawn_watched,
)

# RM states, ordered so sorting gives a canonical symmetry representative
WORKING = "working"
PREPARED = "prepared"
COMMITTED = "committed"
ABORTED = "aborted"

# TM states
TM_INIT = "init"
TM_COMMITTED = "committed"
TM_ABORTED = "aborted"


@dataclass(frozen=True)
class TwoPhaseState:
    rm_state: tuple  # one of the RM states per RM
    tm_state: str
    tm_prepared: tuple  # bool per RM
    msgs: frozenset  # ("prepared", rm) | ("commit",) | ("abort",)

    def representative(self) -> "TwoPhaseState":
        """Sort RM states (with their tm_prepared flags) and rewrite RM
        indices inside messages (reference ``2pc.rs:165-182``)."""
        plan = RewritePlan.from_values_to_sort(self.rm_state)
        return TwoPhaseState(
            rm_state=tuple(plan.reindex(self.rm_state)),
            tm_state=self.tm_state,
            tm_prepared=tuple(plan.reindex(self.tm_prepared)),
            msgs=frozenset(
                ("prepared", plan.mapping[m[1]]) if m[0] == "prepared" else m
                for m in self.msgs
            ),
        )


@dataclass
class TwoPhaseSys(TensorBackedModel, Model):
    """Abstract 2PC over ``rm_count`` resource managers
    (reference ``2pc.rs:43-121``)."""

    rm_count: int

    def tensor_model(self) -> "TwoPhaseTensor":
        return TwoPhaseTensor(self)

    def sweep_family(self, n: int = 8):
        """Default hyper-batched sweep for the STATERIGHT_TPU_SWEEP env
        knob (docs/sweep.md): delegates to the module-level family."""
        return sweep_family(n)

    def init_states(self):
        n = self.rm_count
        return [
            TwoPhaseState(
                rm_state=(WORKING,) * n,
                tm_state=TM_INIT,
                tm_prepared=(False,) * n,
                msgs=frozenset(),
            )
        ]

    def actions(self, state: TwoPhaseState):
        acts = []
        if state.tm_state == TM_INIT and all(state.tm_prepared):
            acts.append(("tm_commit",))
        if state.tm_state == TM_INIT:
            acts.append(("tm_abort",))
        for rm in range(self.rm_count):
            if state.tm_state == TM_INIT and ("prepared", rm) in state.msgs:
                acts.append(("tm_rcv_prepared", rm))
            if state.rm_state[rm] == WORKING:
                acts.append(("rm_prepare", rm))
                acts.append(("rm_choose_abort", rm))
            if ("commit",) in state.msgs:
                acts.append(("rm_rcv_commit", rm))
            if ("abort",) in state.msgs:
                acts.append(("rm_rcv_abort", rm))
        return acts

    def next_state(self, state: TwoPhaseState, action) -> Optional[TwoPhaseState]:
        kind = action[0]
        if kind == "tm_rcv_prepared":
            rm = action[1]
            prepared = list(state.tm_prepared)
            prepared[rm] = True
            return replace(state, tm_prepared=tuple(prepared))
        if kind == "tm_commit":
            return replace(
                state, tm_state=TM_COMMITTED, msgs=state.msgs | {("commit",)}
            )
        if kind == "tm_abort":
            return replace(
                state, tm_state=TM_ABORTED, msgs=state.msgs | {("abort",)}
            )
        rm = action[1]
        rm_state = list(state.rm_state)
        if kind == "rm_prepare":
            rm_state[rm] = PREPARED
            return replace(
                state,
                rm_state=tuple(rm_state),
                msgs=state.msgs | {("prepared", rm)},
            )
        if kind == "rm_choose_abort":
            rm_state[rm] = ABORTED
        elif kind == "rm_rcv_commit":
            rm_state[rm] = COMMITTED
        elif kind == "rm_rcv_abort":
            rm_state[rm] = ABORTED
        else:
            raise ValueError(action)
        return replace(state, rm_state=tuple(rm_state))

    def properties(self):
        return [
            Property.sometimes(
                "abort agreement",
                lambda m, s: all(x == ABORTED for x in s.rm_state),
            ),
            Property.sometimes(
                "commit agreement",
                lambda m, s: all(x == COMMITTED for x in s.rm_state),
            ),
            Property.always(
                "consistent",
                lambda m, s: not (
                    ABORTED in s.rm_state and COMMITTED in s.rm_state
                ),
            ),
        ]


# ---------------------------------------------------------------------------
# Tensor form (device twin)
# ---------------------------------------------------------------------------

# Numeric RM-state codes for the row encoding.
_RM_CODE = {WORKING: 0, PREPARED: 1, COMMITTED: 2, ABORTED: 3}
_RM_NAME = {v: k for k, v in _RM_CODE.items()}
_TM_CODE = {TM_INIT: 0, TM_COMMITTED: 1, TM_ABORTED: 2}
_TM_NAME = {v: k for k, v in _TM_CODE.items()}


class TwoPhaseTensor(TensorModel):
    """u64-row encoding of :class:`TwoPhaseState` with a static-arity jittable
    transition (the SURVEY §7 "minimum end-to-end slice" model).

    Layout (word-aligned by :class:`BitPacker`): ``rm`` packs 2 bits per RM;
    ``tm`` 2 bits; ``tm_prepared`` / ``msg_prepared`` one bit per RM;
    ``msg_commit`` / ``msg_abort`` one bit each.  The monotone message *set*
    of the object form (reference ``2pc.rs:16-21``) becomes a bitmask, which
    is automatically canonical — equal sets encode to equal words.

    Static action arity A = 2 + 5·rm_count, slots ordered:
    ``tm_commit, tm_abort,`` then per RM ``tm_rcv_prepared, rm_prepare,
    rm_choose_abort, rm_rcv_commit, rm_rcv_abort``.
    """

    def __init__(self, sys: TwoPhaseSys):
        n = sys.rm_count
        if n > 29:
            raise ValueError("tensor 2PC supports up to 29 RMs per word")
        self.model = sys
        self.n = n
        self.packer = BitPacker(
            [
                ("rm", 2 * n),
                ("tm", 2),
                ("tm_prepared", n),
                ("msg_prepared", n),
                ("msg_commit", 1),
                ("msg_abort", 1),
            ]
        )
        self.width = self.packer.width
        self.max_actions = 2 + 5 * n

    # -- host bridge ---------------------------------------------------------

    def encode_state(self, s: TwoPhaseState) -> tuple:
        rm = 0
        for i, st in enumerate(s.rm_state):
            rm |= _RM_CODE[st] << (2 * i)
        prep = sum(1 << i for i, p in enumerate(s.tm_prepared) if p)
        mprep = sum(1 << m[1] for m in s.msgs if m[0] == "prepared")
        return self.packer.pack(
            rm=rm,
            tm=_TM_CODE[s.tm_state],
            tm_prepared=prep,
            msg_prepared=mprep,
            msg_commit=int(("commit",) in s.msgs),
            msg_abort=int(("abort",) in s.msgs),
        )

    def decode_state(self, row) -> TwoPhaseState:
        f = self.packer.unpack(row)
        n = self.n
        msgs = set()
        for i in range(n):
            if (f["msg_prepared"] >> i) & 1:
                msgs.add(("prepared", i))
        if f["msg_commit"]:
            msgs.add(("commit",))
        if f["msg_abort"]:
            msgs.add(("abort",))
        return TwoPhaseState(
            rm_state=tuple(_RM_NAME[(f["rm"] >> (2 * i)) & 3] for i in range(n)),
            tm_state=_TM_NAME[f["tm"]],
            tm_prepared=tuple(bool((f["tm_prepared"] >> i) & 1) for i in range(n)),
            msgs=frozenset(msgs),
        )

    def init_rows(self):
        import numpy as np

        rows = [self.encode_state(s) for s in self.model.init_states()]
        return np.asarray(rows, dtype=np.uint64)

    def representative_rows(self, rows):
        """Vectorized symmetry canonicalizer: the device analogue of
        :meth:`TwoPhaseState.representative` (stable sort of RM sub-states,
        reindexing ``tm_prepared`` and the ``prepared`` message bits by the
        same permutation).  Must replicate the object form *exactly* — the
        host sorts the RM state **strings** ("aborted" < "committed" <
        "prepared" < "working"), which is the reverse of the 2-bit codes, so
        the device sort key is ``3 - code``; its stable rank then is the
        identical permutation, preserving the pinned symmetry counts
        (665 @ 5 RMs, reference ``2pc.rs:138``).

        No sort and no gather: each RM's rank comes from compares over the
        candidate lanes (``stable_rank``) and its three fields are shifted
        straight to that rank in the packed words (``pack_by_rank``).  The
        ``argsort`` + three ``take_along_axis`` this replaces were 5.9 s of a
        7.35 s 2pc-13 check (12 ns a gathered lane; PERF.md section 6,
        PR 34)."""
        import jax.numpy as jnp

        n, pk = self.n, self.packer
        u64 = jnp.uint64

        def columns(name, bits):
            field = pk.get(rows, name)
            return [
                ((field >> u64(bits * i)) & u64((1 << bits) - 1)).astype(jnp.int32)
                for i in range(n)
            ]

        fields = {"rm": 2, "tm_prepared": 1, "msg_prepared": 1}
        cols = {name: columns(name, bits) for name, bits in fields.items()}
        ranks = stable_rank([3 - code for code in cols["rm"]])  # old -> new
        for name, bits in fields.items():
            rows = pk.set(rows, name, pack_by_rank(cols[name], ranks, bits))
        return rows

    # -- device --------------------------------------------------------------

    def step_rows(self, rows):
        return self._step_rows_impl(rows, coalesce=False)

    def step_rows_coalesced(self, rows):
        """Expand-scatter-coalesced step (``ops/mxu.py``, docs/roofline.md):
        the same transition function with each action's packed-word
        write-backs assembled as ONE word-stacked block (``FieldWriter``
        coalesced mode) instead of one full-block slice read + scatter
        per written field.  Successors and validity are bit-identical to
        :meth:`step_rows` (whole-space parity pinned in tests); only the
        assembly shape changes.  Selected by the engines under
        ``CheckerBuilder.mxu()`` / ``--mxu``."""
        return self._step_rows_impl(rows, coalesce=True)

    def _step_rows_impl(self, rows, coalesce):
        import jax.numpy as jnp

        pk, n = self.packer, self.n
        one = jnp.uint64(1)
        rm = pk.get(rows, "rm")
        tm = pk.get(rows, "tm")
        prep = pk.get(rows, "tm_prepared")
        mprep = pk.get(rows, "msg_prepared")
        mc = pk.get(rows, "msg_commit")
        ma = pk.get(rows, "msg_abort")

        tm_init = tm == jnp.uint64(0)
        all_prepared = prep == jnp.uint64((1 << n) - 1)

        succs, valids = [], []

        def emit(valid, fw):
            valids.append(valid)
            succs.append(fw.done())

        def w():  # one writer per action, all reads come from `rows`
            return FieldWriter(pk, rows, coalesce=coalesce)

        # tm_commit / tm_abort
        emit(
            tm_init & all_prepared,
            w().set("tm", jnp.uint64(1)).set("msg_commit", jnp.ones_like(mc)),
        )
        emit(
            tm_init,
            w().set("tm", jnp.uint64(2)).set("msg_abort", jnp.ones_like(ma)),
        )

        for i in range(n):
            bit = jnp.uint64(1 << i)
            rm_i = (rm >> jnp.uint64(2 * i)) & jnp.uint64(3)
            rm_clear = rm & jnp.uint64(~(3 << (2 * i)) & ((1 << (2 * n)) - 1))

            # tm_rcv_prepared(i)
            emit(
                tm_init & ((mprep >> jnp.uint64(i)) & one == one),
                w().set("tm_prepared", prep | bit),
            )
            # rm_prepare(i): rm working -> prepared + send prepared msg
            emit(
                rm_i == jnp.uint64(0),
                w()
                .set("rm", rm_clear | (jnp.uint64(1) << jnp.uint64(2 * i)))
                .set("msg_prepared", mprep | bit),
            )
            # rm_choose_abort(i)
            emit(
                rm_i == jnp.uint64(0),
                w().set("rm", rm_clear | (jnp.uint64(3) << jnp.uint64(2 * i))),
            )
            # rm_rcv_commit(i)
            emit(
                mc == one,
                w().set("rm", rm_clear | (jnp.uint64(2) << jnp.uint64(2 * i))),
            )
            # rm_rcv_abort(i)
            emit(
                ma == one,
                w().set("rm", rm_clear | (jnp.uint64(3) << jnp.uint64(2 * i))),
            )

        succ = jnp.stack(succs, axis=-2)  # [B, A, W]
        valid = jnp.stack(valids, axis=-1)  # [B, A]
        return succ, valid

    def property_masks(self, rows):
        import jax.numpy as jnp

        pk, n = self.packer, self.n
        rm = pk.get(rows, "rm")
        all_aborted = rm == jnp.uint64((1 << (2 * n)) - 1)  # 0b11 per RM
        all_committed = rm == jnp.uint64(int("10" * n, 2))  # 0b10 per RM
        any_committed = jnp.zeros(rows.shape[:-1], bool)
        any_aborted = jnp.zeros(rows.shape[:-1], bool)
        for i in range(n):
            rm_i = (rm >> jnp.uint64(2 * i)) & jnp.uint64(3)
            any_committed |= rm_i == jnp.uint64(2)
            any_aborted |= rm_i == jnp.uint64(3)
        consistent = ~(any_committed & any_aborted)
        # order matches TwoPhaseSys.properties()
        return jnp.stack([all_aborted, all_committed, consistent], axis=-1)


def _audit_models(rest=()):
    """Default configurations for the static auditor (``audit`` verb and
    the fleet runner, ``_cli.fleet_audit``)."""
    rm_count = int(rest[0]) if rest else 3
    return [(f"two_phase_commit rm={rm_count}", TwoPhaseSys(rm_count))]


def sweep_family(n: int = 8):
    """The 2pc default sweep (docs/sweep.md; ``sweep`` verb +
    ``STATERIGHT_TPU_SWEEP``): ``n`` rm=3 instances under distinct table
    seeds — same dynamics, disjoint fingerprint namespaces, ONE shape
    cohort / ONE engine compile; a hash/table-seed fuzz whose per-seed
    counts must all reconcile to the sequential 288/1146."""
    from ..sweep import SweepInstance, SweepSpec

    return SweepSpec([
        SweepInstance(
            f"2pc3-seed{i}", TwoPhaseSys(3),
            params={"rm": 3, "seed": i}, seed=i,
        )
        for i in range(max(1, int(n)))
    ])


def main(argv=None):
    def check(rest):
        rm_count = int(rest[0]) if rest else 2
        print(f"Checking two phase commit with {rm_count} resource managers.")
        TwoPhaseSys(rm_count).checker().threads(default_threads()).spawn_dfs().report()

    def check_sym(rest):
        rm_count = int(rest[0]) if rest else 2
        print(
            f"Checking two phase commit with {rm_count} resource managers"
            " using symmetry reduction."
        )
        TwoPhaseSys(rm_count).checker().threads(
            default_threads()
        ).symmetry().spawn_dfs().report()

    def check_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        rm_count = int(rest[0]) if rest else 2
        print(
            f"Checking two phase commit with {rm_count} RMs on TPU"
            + (" (checked mode)." if checked else ".")
        )
        m = apply_encoding(TwoPhaseSys(rm_count), perf)
        spawn_watched(
            apply_perf(m.checker().checked(checked), perf),
            watch, lambda b: b.spawn_tpu(),
        ).report()

    def check_sym_tpu(rest):
        checked, rest = pop_checked(rest)
        perf, rest = pop_perf(rest)
        watch, rest = pop_watch(rest)
        rm_count = int(rest[0]) if rest else 2
        print(
            f"Checking two phase commit with {rm_count} RMs on TPU "
            "using symmetry reduction"
            + (" (checked mode)." if checked else ".")
        )
        m = apply_encoding(TwoPhaseSys(rm_count), perf)
        spawn_watched(
            apply_perf(m.checker().checked(checked).symmetry(), perf),
            watch, lambda b: b.spawn_tpu(),
        ).report()

    def check_auto(rest):
        rm_count = int(rest[0]) if rest else 2
        print(
            f"Checking two phase commit with {rm_count} RMs "
            "(auto engine selection)."
        )
        TwoPhaseSys(rm_count).checker().threads(
            default_threads()
        ).spawn_auto().report()

    def explore(rest):
        rm_count = int(rest[0]) if rest else 2
        addr = rest[1] if len(rest) > 1 else "localhost:3000"
        print(f"Exploring 2PC state space with {rm_count} RMs on {addr}.")
        TwoPhaseSys(rm_count).checker().serve(addr)

    def supervise(rest):
        opts, rest = pop_supervise_opts(rest)
        rm_count = int(rest[0]) if rest else 2
        print(
            f"Supervised 2PC check with {rm_count} RMs "
            "(autosave + retry/backoff; docs/robustness.md)."
        )
        run_supervised(TwoPhaseSys(rm_count).checker(), opts)

    run_cli(
        "  two_phase_commit check [RESOURCE_MANAGER_COUNT]\n"
        "  two_phase_commit check-sym [RESOURCE_MANAGER_COUNT]\n"
        "  two_phase_commit check-tpu [RESOURCE_MANAGER_COUNT]\n"
        "  two_phase_commit check-sym-tpu [RESOURCE_MANAGER_COUNT]\n"
        "  two_phase_commit check-auto [RESOURCE_MANAGER_COUNT]\n"
        "  two_phase_commit explore [RESOURCE_MANAGER_COUNT] [ADDRESS]\n"
        "  two_phase_commit sweep [N_INSTANCES]",
        check,
        check_sym=check_sym,
        check_tpu=check_tpu,
        check_sym_tpu=check_sym_tpu,
        check_auto=check_auto,
        explore=explore,
        audit=make_audit_cmd(_audit_models),
        sanitize=make_sanitize_cmd(_audit_models),
        independence=make_independence_cmd(_audit_models),
        profile=make_profile_cmd(_audit_models),
        report=make_report_cmd(_audit_models),
        capacity=make_capacity_cmd(_audit_models),
        costmodel=make_costmodel_cmd(_audit_models),
        compare=make_compare_cmd(),
        supervise=supervise,
        sweep=make_sweep_cmd(sweep_family),
        argv=argv,
    )


if __name__ == "__main__":
    main()
