"""What a compiled program sends: a census of the collectives in HLO text.

The sharding of a step is stated in ``models/*.py`` and ``sharding.py`` and
decided by the partitioner; what it decided is only in the compiled
program. ``census(compiled.as_text())`` lists every collective there once,
with the bytes a chip holds of its result, the size of its replica group,
where it runs and the JAX operation it came from.
``tests/test_chip_compile.py`` holds the GPT-J step to its census.
``kernel_census`` counts the same program's Pallas kernel calls by name:
how often a step runs ``flash_fwd`` says whether its backward pass keeps
the kernel's outputs or runs it again (``models/lm.py: scan_blocks``). The
four-chip benchmark cell's real step, compiled for a described ``v5e:2x2``
without a chip (25 s) and read:

    XLA_FLAGS=--xla_dump_to=/tmp/step python benchmark/rehearse.py \\
        --workload gptj-6b-4chip.steady --skip-tiny
    python -m ray_tpu.parallel.collectives \\
        /tmp/step/*jit_step*after_optimizations.txt
"""

from __future__ import annotations

import collections
import math
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute", "collective-broadcast")

_ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
             "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
             "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_ARRAY = re.compile(r"\b(f8\w*|[a-z]+\d+|pred)\[([\d,]*)\]")
_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
# What a schedule lists between two instructions without running anything.
_BOOKKEEPING = frozenset({"get-tuple-element", "bitcast", "tuple", "constant",
                          "parameter", "copy-start", "copy-done",
                          "partition-id", "replica-id"})
_CALLEES = re.compile(
    r"\b(calls|to_apply|body|condition|branch_computations|"
    r"called_computations)=(?:\{([^}]*)\}|(%?[\w.\-]+))")


def _arrays(shape: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each array in a shape's text, a tuple's in order."""
    return [(dtype, tuple(int(n) for n in dims.split(",") if n))
            for dtype, dims in _ARRAY.findall(shape)]


def _split_shape(rest: str) -> Tuple[str, str]:
    """``<shape> <opcode>(...`` -> (shape, what follows it). A tuple's
    shape holds spaces and layouts hold parentheses, so it is matched."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i + 1], rest[i + 2:]
    return rest, ""


def _group_size(attrs: str) -> Optional[int]:
    iota = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if iota:
        return int(iota.group(2))
    listed = re.search(r"replica_groups=\{\{([\d,]*)\}", attrs)
    if listed:
        return len(listed.group(1).split(","))
    if "source_target_pairs" in attrs:
        return 2
    return None


def census(hlo_text: str) -> List[Dict[str, Any]]:
    """Every collective of a compiled program's text, once each:

        kind         one of ``KINDS``
        arrays       [(dtype, dims)] of the result a chip holds (of an
                     ``all-gather-start`` or ``collective-permute-start``
                     the result's half of the pair)
        bytes        of those arrays, summed
        group_size   chips in one replica group (None where not stated)
        computation  the computation it runs in, fusions and calls looked
                     through: ``ENTRY``'s name, a while loop's body, ...
        in_loop      whether that lies, however deep, in a while loop
        op_name      the JAX operation in its metadata ("" if none)
        name         the instruction's
        is_async     whether it is a pair of a start and a done, between
                     which the chip is free to run other instructions
        between      the names of the instructions scheduled between the
                     two, in order (a compiled TPU program's text is its
                     schedule), bookkeeping left out; [] if synchronous
        matmuls_between  how many of those are matmuls: a fusion around a
                     ``convolution`` / ``dot``, or one on its own

    An asynchronous pair counts at its ``-start`` (several ``ppermute``s of
    one ``shard_map`` share a ``channel_id`` and are several pairs); the TPU
    compiler's other form, in which one collective is repeated in the
    fusions that start it, carry it and finish it, counts once by its
    ``channel_id``, from the first of those fusions to the last.
    """
    computations: Dict[str, List[Tuple[str, str]]] = {}
    current = None
    for line in hlo_text.splitlines():
        header = _HEADER.match(line)
        if header:
            current = header.group(1)
            computations[current] = []
        elif current is not None:
            found = _INSTRUCTION.match(line)
            if found:
                computations[current].append(found.groups())

    # computation -> (the computation that calls it, is it a loop's body)
    called_from: Dict[str, Tuple[str, bool]] = {}
    for caller, instructions in computations.items():
        for _, rest in instructions:
            for how, listed, single in _CALLEES.findall(rest):
                for callee in (listed or single).replace("%", "").split(", "):
                    called_from.setdefault(
                        callee, (caller, how in ("body", "condition")))

    def place(computation: str) -> Tuple[str, bool]:
        """(the nearest enclosing loop body or ENTRY, inside any loop)."""
        nearest, in_loop = None, False
        while computation in called_from:
            caller, is_loop = called_from[computation]
            if is_loop:
                nearest, in_loop = nearest or computation, True
            computation = caller
        return nearest or computation, in_loop

    # fused computation -> (the computation its fusion sits in, where)
    fused_at: Dict[str, Tuple[str, int]] = {}
    multiplies = set()
    for caller, instructions in computations.items():
        for index, (_, rest) in enumerate(instructions):
            opcode = _split_shape(rest)[1].partition("(")[0]
            if opcode in ("convolution", "dot"):
                multiplies.add(caller)
            callee = re.search(r"\bcalls=%?([\w.\-]+)", rest)
            if opcode == "fusion" and callee:
                fused_at.setdefault(callee.group(1), (caller, index))

    def scheduled_between(computation: str, start: int, done: int):
        """(names, how many are matmuls) of instructions start+1 .. done-1."""
        names, matmuls = [], 0
        for name, rest in computations[computation][start + 1:done]:
            opcode = _split_shape(rest)[1].partition("(")[0]
            if opcode in _BOOKKEEPING:
                continue
            names.append(name)
            callee = re.search(r"\bcalls=%?([\w.\-]+)", rest)
            matmuls += opcode in ("convolution", "dot") or bool(
                callee and callee.group(1) in multiplies)
        return names, matmuls

    found_ops, by_channel = [], {}
    for computation, instructions in computations.items():
        for index, (name, rest) in enumerate(instructions):
            shape, tail = _split_shape(rest)
            opcode = tail.partition("(")[0]
            started = opcode.endswith("-start")
            kind = opcode[:-6] if started else opcode
            if kind not in KINDS:
                continue
            channel = re.search(r"channel_id=(\d+)", tail)
            fused = (kind, channel.group(1)) if channel and not started \
                else None
            if fused in by_channel:  # a later fusion of the same one
                first, entry = by_channel[fused]
                start, done = fused_at.get(first), fused_at.get(computation)
                if start and done and start[0] == done[0] \
                        and start[1] < done[1]:
                    entry["is_async"] = True
                    entry["between"], entry["matmuls_between"] = \
                        scheduled_between(start[0], start[1], done[1])
                continue
            arrays = _arrays(shape)
            if opcode in ("all-gather-start", "collective-permute-start"):
                arrays = arrays[1:2]
            between, matmuls = [], 0
            if started:
                done = next((i for i, (_, other) in enumerate(instructions)
                             if re.search(rf"-done\(%?{re.escape(name)}\)",
                                          other)), index + 1)
                between, matmuls = scheduled_between(computation, index, done)
            op_name = re.search(r'op_name="([^"]*)"', tail)
            where, in_loop = place(computation)
            found_ops.append({
                "kind": kind, "arrays": arrays,
                "bytes": sum(math.prod(dims) * _ITEMSIZE.get(dtype, 1)
                             for dtype, dims in arrays),
                "group_size": _group_size(tail), "computation": where,
                "in_loop": in_loop,
                "op_name": op_name.group(1) if op_name else "",
                "name": name, "is_async": started, "between": between,
                "matmuls_between": matmuls})
            if fused:
                by_channel[fused] = (computation, found_ops[-1])
    return found_ops


_KERNEL_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r'op_name="[^"]*?([\w.\-]+)\)*/pallas_call"')


def sub_jaxprs(eqn: Any) -> Iterator[Any]:
    """The jaxprs an equation holds in its parameters (a ``scan``'s body, a
    ``pjit``'s, a ``cond``'s branches), closed ones opened."""
    for param in eqn.params.values():
        for sub in param if isinstance(param, (list, tuple)) else (param,):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def kernel_census(program: Any, a_step: bool = False) -> Dict[str, int]:
    """How many calls of each Pallas kernel a program holds, by the name
    the kernel was given (``pallas_call(name=...)``). ``program`` is a
    compiled program's HLO text, where every ``tpu_custom_call`` counts, or
    a jaxpr, where every ``pallas_call`` equation does, the interpreted
    ones of a CPU trace too, sub-jaxprs looked through. A call in a loop's
    body is one call, however often the loop runs: a layer scan with its
    backward scan holds each kernel of its block once or twice. With
    ``a_step`` (a jaxpr's alone) it counts as often as its ``scan``s run it:
    the calls one run of the program executes."""
    if isinstance(program, str):
        return dict(collections.Counter(_KERNEL_CALL.findall(program)))
    counts: collections.Counter = collections.Counter()

    def walk(jaxpr, runs):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[eqn.params["name"]] += runs
            inside = runs * eqn.params["length"] \
                if a_step and eqn.primitive.name == "scan" else runs
            for sub in sub_jaxprs(eqn):
                walk(sub, inside)

    walk(getattr(program, "jaxpr", program), 1)
    return dict(counts)


def main(argv: Optional[List[str]] = None) -> None:
    """Print the census of an HLO text file, largest first, under its
    count of kernel calls; an optional second argument is the least size
    in MB worth a line."""
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        text = f.read()
    ops = census(text)
    least = float(argv[1]) * 1e6 if len(argv) > 1 else 0.0
    print(kernel_census(text))
    print(dict(collections.Counter(op["kind"] for op in ops)))
    for op in sorted(ops, key=lambda op: -op["bytes"]):
        if op["bytes"] >= least:
            shapes = " ".join(f"{dtype}{list(dims)}"
                              for dtype, dims in op["arrays"])
            flight = (f"async, {op['matmuls_between']} matmuls of "
                      f"{len(op['between'])} between"
                      if op["is_async"] else "sync")
            print(f"{op['kind']:<18} {op['bytes'] / 1e6:>8.1f} MB  over "
                  f"{op['group_size']}  {'loop' if op['in_loop'] else 'once'}"
                  f"  {flight}  {op['name']}  {shapes}  {op['op_name']}")


if __name__ == "__main__":
    main()
