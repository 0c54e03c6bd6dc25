#!/usr/bin/env python3
"""A partitioned cell's train segment, compiled for TPUs that are
described and not attached, and its schedule read: where the
Partitioned-step layer starts from, before a four-chip call is spent.

    JAX_PLATFORMS=cpu python3 scripts/partitioned_schedule.py \\
        [--cell alexnet227-dp4.resident] [--topology v5e:2x2] \\
        [--options SET ...] [--backward] [--partitioner] [--text DIR]

Here, on the CPU (~1 min a compile at AlexNet's size). Builds the
cell's workflow and ``GSPMDTrainer`` from its configuration file as
``benchmark/builders/convnet.py`` does, on a mesh of forced host
devices and over one minibatch of samples (only shapes matter), takes
the function the trainer hands to ``_compile_train``, moves the
trainer's mesh onto the described topology and lets ``_compile_train``
jit it again there; compiled once a ``--options`` SET: ``none`` (the
default: the program as the trainer compiles it) or
``NAME=VALUE,NAME=VALUE`` (those ``compiler_options``, a diagnostic:
the trainer sets none). The data set is lowered at the traffic file's
size.

The compiled module is scheduled (``is_scheduled=true``): the order
of the instructions in a computation is the order the core issues
them in. Printed: every collective of the train step in that order,
its payload, whether it is one half of an asynchronous pair and, for
a pair, how many instructions and which convolutions and products
sit between its start and its done (what stands beside it; PR 34
measured that on a v5e it hides next to nothing: the all-reduce is
work of the core), the minibatch fetch's marked ``input`` (the planned
exchange's all-to-all; with ``--partitioner`` the program a sweep
takes whose order overflows the exchange's capacity, which all-reduces
the whole padded global minibatch); the gradient all-reduces' count as
pairs and bytes as synchronous ones; ``memory_analysis()``. With ``--backward`` also
every instruction of the step's body that runs something, from the
first of the backward pass on, with the units' scopes found inside it
(``B`` backward, ``F`` forward, ``*`` a convolution, product or
pooling): what the compiler fused with what, and what it sank where.
No time comes out of it, and nothing here is a device number.
"""

import argparse
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse_options(text):
    """One ``--options`` SET -> the ``compiler_options`` to compile
    with (``none``: an empty dict)."""
    options = {}
    for pair in text.split(","):
        if pair != "none":
            name, _, value = pair.partition("=")
            options[name] = {"true": True, "false": False}.get(
                value.lower(), int(value) if value.lstrip("-").isdigit()
                else value)
    return options


DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8}
#: an HLO computation's header: ``%name (params) -> type {``
COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s+\(.*\{\s*$")
#: ``%name = type opcode(``, the type an array's or a tuple's
INSTRUCTION_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\(")
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(-start|-done)?$")
#: instructions that run nothing
NO_WORK = frozenset((
    "parameter", "get-tuple-element", "bitcast", "tuple", "while",
    "opt-barrier", "call", "conditional", "constant", "partition-id"))
#: the ``op_name`` of what a core's matrix unit runs
PRODUCT_RE = re.compile(r"(?:conv_general_dilated|dot_general)$")
#: a unit's pass, or a scope of the step's own, in an ``op_name``
SCOPE_RE = re.compile(r"transpose\(jvp\(veles\.(u\d+)|jvp\(veles\.(u\d+)|"
                      r"veles\.(update\.u\d+|in|loss|gradnorm)")
HEAVY = frozenset(("convolution", "dot", "select-and-scatter",
                   "reduce-window"))


def payload_bytes(result):
    """Bytes of every array an HLO result type names."""
    total = 0
    for dtype, dims in re.findall(r"([a-z]\w*)\[([0-9,]*)\]", result):
        n = DTYPE_BYTES.get(dtype, 0)
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def op_name(line):
    found = re.search(r'op_name="([^"]*)"', line)
    return found.group(1) if found else ""


def computations_of(text):
    """``({computation: [(match, line)]}, {fused computations})`` of
    an HLO module's text, instructions in the text's order."""
    computations, fused, name = {}, set(), None
    for line in text.splitlines():
        header = COMPUTATION_RE.match(line)
        if header:
            name = header.group(1)
            computations[name] = []
            continue
        found = INSTRUCTION_RE.match(line)
        if found is None or name is None:
            continue
        computations[name].append((found, line))
        if found.group(3) == "fusion":
            fused.update(re.findall(r"calls=(%[\w.\-]+)", line))
    return computations, fused


def collective_schedule(text):
    """The collectives of a SCHEDULED HLO module ``text``, in the
    order the core issues them, one dict each:

    ``name``, ``kind`` (``all-reduce``, ...), ``form`` (``sync``,
    ``start`` or ``done``), ``payload`` (the collective's own result
    type) and its ``bytes``, ``op_name`` (the scope whose value it
    moves) and ``gradient`` (an all-reduce of a backward value,
    ``transpose(jvp(...))``: under data parallelism a parameter's
    gradient). A ``done`` also says what the schedule put between it
    and its ``start``: ``between`` (instructions that run something),
    ``products`` (the ``op_name`` of every convolution or matrix
    product among them) and ``continuations`` (those fused with the
    collective itself).

    Both spellings of an asynchronous pair are read: XLA's own
    (``all-reduce-start`` / ``all-reduce-done``) and the TPU
    compiler's asynchronous collective fusions
    (``%async-collective-start.3`` / ``%async-collective-done.3``:
    fusions whose called computation holds the ``all-reduce``, with
    continuation fusions between them that run a product beside it).
    A fusion that holds a collective and is no half of a pair (the
    TPU's ``all-reduce-scatter``) is synchronous."""
    computations, fused = computations_of(text)
    # the collective a fused computation holds: kind, payload, op_name
    held = {}
    for name in fused:
        for found, line in computations.get(name, ()):
            kind = COLLECTIVE_RE.match(found.group(3))
            if kind:
                held[name] = (kind.group(1), found.group(2), op_name(line))
    rows = []
    for name, instructions in computations.items():
        if name in fused:
            continue
        open_starts = {}
        for position, (found, line) in enumerate(instructions):
            own, result, opcode = found.groups()
            kind = COLLECTIVE_RE.match(opcode)
            called = re.search(r"calls=(%[\w.\-]+)", line).group(1) \
                if opcode == "fusion" else None
            if kind:
                what, form = kind.group(1), (kind.group(2) or "-sync")[1:]
                payload, scope = result, op_name(line)
            elif called in held and "async_collective_fusion" not in called:
                # (a continuation fusion is counted with its pair, below)
                what, payload, scope = held[called]
                form = ("start" if "collective-start" in own else
                        "done" if "collective-done" in own else "sync")
            else:
                continue
            row = {"name": own, "kind": what, "form": form,
                   "payload": payload, "op_name": scope,
                   "bytes": payload_bytes(payload),
                   "gradient": what == "all-reduce"
                   and "transpose(" in scope}
            if form == "start":
                open_starts[own] = position
            elif form == "done":
                operands = re.findall(r"%[\w.\-]+", line[found.end():])
                start = next((o for o in operands if o in open_starts),
                             own.replace("-done", "-start"))
                begin = open_starts.pop(start, None)
                between = [] if begin is None else [
                    (f, ln) for f, ln in instructions[begin + 1:position]
                    if f.group(3) not in NO_WORK]
                row["between"] = len(between)
                row["products"] = [
                    op_name(ln) for _, ln in between
                    if PRODUCT_RE.search(op_name(ln))]
                row["continuations"] = [
                    op_name(ln) for f, ln in between
                    if "calls=%async_collective_fusion" in ln]
            rows.append(row)
    return rows


def format_collective(row):
    """One line of text for a row of :func:`collective_schedule`."""
    scope = row["op_name"].split("closed_call/")[-1]
    line = "%-5s %-18s %9.3f MB  %s%s" % (
        row["form"], row["kind"], row["bytes"] / 1e6,
        "gradient  " if row["gradient"] else
        "input  " if "veles.in" in row["op_name"] else "", scope[-70:])
    if row["form"] == "done":
        units = sorted(set(re.findall(r"veles\.(u\d+)", " ".join(
            row["products"]))))
        line += "\n      between its halves: %d instructions, %d " \
            "products (%s), %d fused with it" % (
                row["between"], len(row["products"]), " ".join(units),
                len(row["continuations"]))
    return line


def backward_schedule(text):
    """The step's body from the first instruction of the backward pass
    on, in the order the core issues it: ``(position, name, opcode,
    result type, {scope: heavy})`` for every instruction that runs
    something, the scopes those of the fused computation where it is a
    fusion (``Bu03``: unit 3's backward, ``Fu03`` its forward, and the
    step's own ``in``, ``loss``, ``gradnorm``, ``update.u03``); heavy:
    a convolution, product or pooling stands under that scope there."""
    computations, fused = computations_of(text)

    def scopes(instructions):
        found = {}
        for match, line in instructions:
            tag = SCOPE_RE.search(op_name(line))
            if tag:
                tag = "B" + tag.group(1) if tag.group(1) else \
                    "F" + tag.group(2) if tag.group(2) else tag.group(3)
                found[tag] = found.get(tag, False) or \
                    match.group(3) in HEAVY
        return found

    def backward_instructions(name):
        return sum(any(tag[0] == "B" for tag in scopes([pair]))
                   for pair in computations[name])

    body = max((name for name in computations if name not in fused),
               key=backward_instructions)
    rows, started = [], False
    for position, (match, line) in enumerate(computations[body]):
        own, result, opcode = match.groups()
        if opcode in NO_WORK and opcode != "opt-barrier" or \
                opcode.startswith(("slice-", "copy-")) or \
                opcode in ("custom-call", "iota"):
            continue
        called = re.search(r"calls=(%[\w.\-]+)", line)
        inside = scopes(computations.get(called.group(1), ())
                        if called else ()) or scopes([(match, line)])
        started = started or any(tag[0] == "B" for tag in inside)
        if started:
            rows.append((position, own, opcode, result, inside))
    return rows


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cell", default="alexnet227-dp4.resident")
    parser.add_argument("--topology", default="v5e:2x2")
    parser.add_argument("--options", action="append", metavar="SET",
                        help="none | NAME=VALUE,...; one compile each")
    parser.add_argument("--backward", action="store_true",
                        help="print the backward pass in schedule order")
    parser.add_argument("--partitioner", action="store_true",
                        help="the program over a plain index matrix")
    parser.add_argument("--text", help="directory for the optimized HLO")
    args = parser.parse_args()

    from benchmark import harness
    bench = harness.Benchmark(ROOT)
    cell = bench.cell(args.cell)
    config, traffic = bench.config(cell), bench.traffic(cell)
    if config.get("trainer") != "gspmd":
        parser.error("%s is not a partitioned cell" % args.cell)
    chips = cell["chips"]
    flag = "--xla_force_host_platform_device_count=%d" % chips
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmark import flops
    from benchmark.seeded_loader import SeededImageLoader
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.nn.base import ForwardBase
    from veles_tpu.nn.precision import set_policy
    from veles_tpu.parallel import dp, gspmd
    from veles_tpu.parallel.mesh import named_sharding
    from veles_tpu.standard_workflow import StandardWorkflow

    jax.config.update("jax_enable_compilation_cache", False)
    set_policy(config["precision"])
    prng.get().seed(1)
    prng.get("loader").seed(2)
    ForwardBase.fill_matrices = lambda self, mem: None  # shapes only
    side, _, channels = flops.input_shape(config)
    optimizer, batch = config["optimizer"], config["batch"]
    traced = {}

    class Capturing(gspmd.GSPMDTrainer):
        def _compile_train(self, fn):
            traced["train_segment"] = fn
            return super()._compile_train(fn)

    t0 = time.time()
    workflow = StandardWorkflow(
        DummyLauncher(),
        loader=lambda wf: SeededImageLoader(
            wf, n_train=batch, n_valid=batch, side=side,
            channels=channels, n_classes=config["classes"], seed=3,
            dtype=config["dataset"]["dtype"], minibatch_size=batch,
            normalization_type=config["normalization"]),
        layers=[dict(layer) for layer in config["layers"]],
        loss=config["loss"], solver=optimizer["solver"],
        learning_rate=optimizer["learning_rate"],
        momentum=optimizer["momentum"],
        weights_decay=optimizer["weights_decay"])
    workflow.initialize(device=Device(backend="cpu"))
    trainer = Capturing(
        workflow, mesh=gspmd.parse_mesh_spec(
            config["mesh"], devices=jax.devices("cpu")[:chips]),
        stream=traffic["stream"])
    params, states = trainer.pull_params()
    print("%s: workflow and trainer in %.0f s; %d parameters" % (
        cell["name"], time.time() - t0,
        sum(v.size for p in params for v in p.values())), flush=True)

    # the same mesh, of described chips: every sharding the trainer
    # builds from here on (in_shardings, the loss's constraint) names
    # them
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    trainer.mesh = gspmd.parse_mesh_spec(
        config["mesh"], devices=topo.devices[:chips])
    trainer._data_spec = named_sharding(trainer.mesh, trainer.axis)
    if trainer._param_shardings is not None:
        trainer._param_shardings = gspmd.gspmd_param_specs(
            workflow.forwards, trainer.mesh)
    repl = named_sharding(trainer.mesh)
    by_step = named_sharding(trainer.mesh, None, trainer.axis)

    def abstract(x, sharding, shape=None):
        return jax.ShapeDtypeStruct(
            jnp.shape(x) if shape is None else shape,
            jnp.result_type(x), sharding=sharding)

    samples = -(-(traffic["n_train"] + traffic["n_valid"]) // chips) * chips
    steps = traffic["n_train"] // batch
    data = tuple(abstract(a, trainer._data_spec,
                          (samples,) + a.shape[1:])
                 for a in trainer._data_args)
    # the index operand of a planned sweep (only shapes matter); with
    # --partitioner the plain matrix of rows a sweep takes whose order
    # overflows the exchange's capacity
    idx = trainer._segment_indices(TRAIN)
    index = idx if args.partitioner else dp.plan_fetch(
        idx, chips, dp.exchange_capacity(batch, chips))[0]
    index = jax.tree_util.tree_map(
        lambda a: abstract(a, by_step, (steps,) + a.shape[1:]), index)
    param_spec = trainer._params_spec()
    if not isinstance(param_spec, (tuple, list)):
        param_spec = tuple(param_spec for _ in params)
    operands = (
        data,
        tuple({k: abstract(v, spec[k] if isinstance(spec, dict) else spec)
               for k, v in layer.items()}
              for layer, spec in zip(params, param_spec)),
        jax.tree_util.tree_map(lambda v: abstract(v, repl), states),
        index,
        abstract(jax.random.PRNGKey(0), repl, (steps, 2)))
    print("mesh %s of %s; %d samples row-sharded, %d steps of %d" % (
        dict(trainer.mesh.shape), args.topology, samples, steps, batch),
        flush=True)
    for number, chosen in enumerate(args.options or ["none"]):
        options = parse_options(chosen)
        print("\n== --options %s: %s" % (
            chosen, options or "the program as the trainer compiles it"),
            flush=True)
        t0 = time.time()
        try:
            compiled = trainer._compile_train(
                traced["train_segment"]).lower(*operands).compile(
                    compiler_options=options)
        except Exception as e:  # an option the compiler refuses
            print("refused: %s" % str(e).splitlines()[0], flush=True)
            continue
        print("compiled in %.0f s" % (time.time() - t0), flush=True)
        text = compiled.as_text()
        if args.text:
            os.makedirs(args.text, exist_ok=True)
            path = os.path.join(args.text, "%s.train_segment.%d.txt" % (
                cell["name"], number))
            with open(path, "w") as f:
                f.write(text)
            print("optimized HLO: %s (%d lines)" % (path,
                                                    text.count("\n")))
        rows = collective_schedule(text)
        for row in rows:
            print(format_collective(row))
        rows = [row for row in rows if row["gradient"]]
        print("gradient all-reduces: %d asynchronous pairs, %.1f MB in "
              "synchronous ones" % (
                  sum(row["form"] == "done" for row in rows),
                  sum(row["bytes"] for row in rows
                      if row["form"] == "sync") / 1e6))
        if args.backward:
            for position, own, opcode, result, inside in \
                    backward_schedule(text):
                print("%5d %-34s %-18s %-30s %s" % (
                    position, own[:34], opcode[:18], result[:30],
                    " ".join(tag + "*" * heavy
                             for tag, heavy in sorted(inside.items()))))
        memory = compiled.memory_analysis()
        print("memory_analysis, a device: arguments %.1f MB, temporaries "
              "%.1f MB, output %.1f MB, aliased %.1f MB" % (
                  memory.argument_size_in_bytes / 1e6,
                  memory.temp_size_in_bytes / 1e6,
                  memory.output_size_in_bytes / 1e6,
                  memory.alias_size_in_bytes / 1e6), flush=True)


if __name__ == "__main__":
    sys.exit(main())
