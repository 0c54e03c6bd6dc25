"""CLI entry point: ``python -m veles_tpu <workflow.py> [config.py] [k=v ...]``.

The reference's ``veles/__main__.py:136-859``: one command runs a model
standalone, as a master (``-l``), as a slave (``-m``), resumes from a
snapshot (``-w``), runs the genetic optimizer (``--optimize``) or an
ensemble (``--ensemble-train``/``--ensemble-test``). A leading
``serve`` subcommand instead starts the dynamic-batching inference
server over a snapshot or export package
(``python -m veles_tpu serve --model ...``, see docs/SERVING.md). Flags are
aggregated from every registered class via the CLI registry
(``veles/cmdline.py``), seeds come from ``-s`` with the reference's
``source:count`` syntax, and config files are Python executed against
the global ``root`` tree.
"""

import importlib.util
import json
import logging
import os
import runpy
import sys

from veles_tpu import cmdline, prng
from veles_tpu.config import apply_config_file, root
from veles_tpu.launcher import Launcher
from veles_tpu.logger import Logger, setup_logging


class Main(Logger):
    """Parse args, seed, load model+config, dispatch the run."""

    EXIT_SUCCESS = 0
    EXIT_FAILURE = 1

    def init_parser(self):
        # import for the side effect of registering their CLI flags
        import veles_tpu.backends  # noqa: F401
        import veles_tpu.loader.base  # noqa: F401
        import veles_tpu.nn.precision  # noqa: F401
        parser = cmdline.init_parser(
            prog="veles_tpu",
            description="TPU-native deep-learning workflow platform")
        parser.add_argument("workflow", nargs="?",
                            help="path to the workflow Python file")
        parser.add_argument("config", nargs="?", default=None,
                            help="path to the config Python file "
                                 "(defaults to <workflow>_config.py)")
        parser.add_argument("overrides", nargs="*", default=[],
                            help="config overrides: root.path.to.key=value")
        parser.add_argument("-s", "--seed", default="1234",
                            help="RNG seed: INT | file:COUNT | "
                                 "/dev/urandom:16 | comma-separated list "
                                 "applied to prng keys default,loader,...")
        parser.add_argument("-w", "--snapshot", default=None,
                            help="resume from a snapshot file")
        parser.add_argument("-i", "--interactive", action="store_true",
                            help="initialize the workflow, then drop "
                                 "into a console with it in scope; "
                                 "call main() there (or exit) to run")
        parser.add_argument("-v", "--verbosity", default="info",
                            choices=["debug", "info", "warning", "error"],
                            help="logging level")
        parser.add_argument("--version", action="store_true",
                            help="print version and exit")
        parser.add_argument("--dump-config", action="store_true",
                            help="print the effective config tree and run")
        parser.add_argument("--dry-run", choices=["init", "exec"],
                            default=None,
                            help="stop after workflow construction (exec) "
                                 "or initialization (init)")
        parser.add_argument("--workflow-graph", default=None,
                            help="write the workflow DOT graph to this file")
        parser.add_argument("--trace-out", default=None, metavar="FILE",
                            help="enable span tracing and dump the trace "
                                 "buffer (Chrome trace-event JSON, open "
                                 "in Perfetto) to FILE at exit; on a "
                                 "master/slave pair pointed at the same "
                                 "FILE the dumps merge into one "
                                 "correlated timeline")
        parser.add_argument("--result-file", default=None,
                            help="write gathered results JSON here")
        parser.add_argument("--optimize", default=None, metavar="GENS:POP",
                            help="run the genetic hyperparameter optimizer")
        parser.add_argument("--ensemble-train", default=None,
                            metavar="N:RATIO",
                            help="train an ensemble of N models on "
                                 "RATIO-subsampled data")
        parser.add_argument("--ensemble-test", default=None, metavar="N",
                            help="evaluate a trained ensemble")
        parser.add_argument("--visualize", default=None, metavar="SNAPSHOT",
                            help="no-op placeholder for plot-only mode")
        return parser

    # -- seeding (``veles/__main__.py:483-537``) ---------------------------

    def _seed_random(self, spec):
        keys = ("default", "loader", "chaos")
        for key, one in zip(keys, str(spec).split(",")):
            self._seed_one(key, one)
        # unseeded keys derive from the first
        for key in keys[len(str(spec).split(",")):]:
            prng.get(key).seed(prng.get(keys[0]).randint(1 << 31))

    def _seed_one(self, key, spec):
        if ":" in spec:
            source, count = spec.rsplit(":", 1)
            with open(source, "rb") as f:
                data = f.read(int(count))
            seed = int.from_bytes(data[:8] or b"\x01", "little")
        else:
            seed = int(spec)
        prng.get(key).seed(seed)

    # -- model / config loading (``__main__.py:396-481``) ------------------

    def _load_model(self, path):
        """Import the workflow file as a module."""
        path = os.path.abspath(path)
        if not os.path.exists(path):
            raise FileNotFoundError(path)
        name = os.path.splitext(os.path.basename(path))[0]
        sys.path.insert(0, os.path.dirname(path))
        try:
            spec = importlib.util.spec_from_file_location(name, path)
            module = importlib.util.module_from_spec(spec)
            sys.modules[name] = module
            spec.loader.exec_module(module)
        finally:
            sys.path.pop(0)
        return module

    def _apply_config(self, path):
        if path and os.path.exists(path):
            apply_config_file(path)
            return True
        return False

    def _override_config(self, overrides):
        """Exec positional ``root.a.b=value`` assignments."""
        for item in overrides:
            if "=" not in item:
                raise ValueError("config override %r is not key=value"
                                 % item)
            exec(item, {"root": root})  # noqa: S102 — reference semantics

    # -- workflow construction ---------------------------------------------

    def _find_workflow_class(self, module):
        from veles_tpu.workflow import Workflow
        candidates = [
            obj for obj in vars(module).values()
            if isinstance(obj, type) and issubclass(obj, Workflow) and
            obj.__module__ == module.__name__]
        if not candidates:
            raise ValueError(
                "%s defines neither run(load, main) nor a Workflow "
                "subclass" % module.__name__)
        # the most derived class defined in the file
        candidates.sort(key=lambda c: len(c.__mro__), reverse=True)
        return candidates[0]

    def _launcher_kwargs(self):
        args = self.args
        kwargs = {
            "backend": getattr(args, "backend", None),
            "testing": getattr(args, "testing", False),
            "slave_death_probability": args.slave_death_probability,
            "job_timeout": args.job_timeout,
            "graphics": getattr(args, "graphics", True),
            "web_status": getattr(args, "web_status", False),
            "nodes": getattr(args, "nodes", None),
            "respawn": getattr(args, "respawn", False),
            "eager": getattr(args, "eager", False),
            "segment_size": getattr(args, "segment_size", 8),
            "pipeline": getattr(args, "pipeline", True),
            "secret_file": getattr(args, "secret_file", None),
            "max_frame_mb": getattr(args, "max_frame_mb", None),
            "interactive": getattr(args, "interactive", False),
            "exchange_dtype": getattr(args, "exchange_dtype", "none"),
            "exchange_eps": getattr(args, "exchange_eps", 0.0),
            "auto_resume": getattr(args, "auto_resume", None),
            "straggler_drop_s": getattr(args, "straggler_drop_s", None),
            "reconnect_s": getattr(args, "reconnect_s", None),
            "gspmd": getattr(args, "gspmd", None),
        }
        if args.listen_address:
            kwargs["listen_address"] = args.listen_address
        if args.master_address:
            kwargs["master_address"] = args.master_address
        return kwargs

    def _load(self, WorkflowClass, **kwargs):
        """Callback handed to the user file's run(load, main)."""
        self.launcher = Launcher(**self._launcher_kwargs())
        if self.args.snapshot:
            from veles_tpu.snapshotter import SnapshotterToFile
            self.workflow = SnapshotterToFile.import_(self.args.snapshot)
            self.workflow.workflow = self.launcher
            snapshot = True
        else:
            self.workflow = WorkflowClass(self.launcher, **kwargs)
            snapshot = False
        return self.workflow, snapshot

    def _main(self, **kwargs):
        """Second callback: initialize and run under the launcher."""
        if self.args.dry_run == "exec":
            return
        self.launcher.initialize(**kwargs)
        if self.args.workflow_graph:
            with open(self.args.workflow_graph, "w") as f:
                f.write(self.workflow.generate_graph())
            self.info("wrote workflow graph to %s",
                      self.args.workflow_graph)
        if self.args.dry_run == "init":
            return
        if getattr(self.args, "interactive", False):
            self._interact()
            if self._run_error is not None:
                # the console swallowed (printed) the training failure;
                # the process exit code must still reflect it
                raise self._run_error
        if not self._ran:
            self._run_and_report()

    def _run_and_report(self):
        if self._ran:
            # -i console: a second main() would retrain from the
            # already-trained weights and silently overwrite the result
            # file — warn and keep the existing results
            self.warning("main() already ran in this session; skipping "
                         "(results were already written)")
            return
        self._ran = True  # even on failure: exiting must NOT retrain
        try:
            self._run_and_report_inner()
        except BaseException as e:
            self._run_error = e
            raise

    def _run_and_report_inner(self):
        self.launcher.run()
        self._write_results()
        # exit reports, as the reference printed at shutdown: slowest
        # units (``veles/workflow.py:788-825``) and peak device memory
        # (``veles/__main__.py:779-797`` + memory.py Watcher)
        self.workflow.print_stats()
        from veles_tpu.memory import watcher
        mem = watcher.report()
        self.info("device memory: %.1f MB in use, %.1f MB peak, "
                  "%d arrays", mem["bytes_in_use"] / 1e6,
                  mem["peak_bytes"] / 1e6, mem["arrays"])

    def _interact(self):
        """-i: console between initialize and run (the TPU-era analog
        of the reference running the whole stack under an IPython
        shell with the reactor in a thread,
        ``veles/launcher.py:119,433-459``; here the scheduler is not
        reactor-driven, so the console simply OWNS the step: call
        ``main()`` inside to train now, or exit and the run resumes).
        """
        ns = {
            "workflow": self.workflow,
            "launcher": self.launcher,
            "units": list(self.workflow.units),
            "root": root,
            "main": self._run_and_report,
        }
        banner = ("\nveles_tpu interactive mode — workflow initialized,"
                  " not yet run.\n"
                  "In scope: workflow, launcher, units, root, main().\n"
                  "main() trains now; exiting the console trains if "
                  "you haven't.")
        use_ipython = sys.stdin.isatty()
        if use_ipython:
            try:
                from IPython.terminal.embed import InteractiveShellEmbed
            except ImportError:
                use_ipython = False
        try:
            if use_ipython:
                InteractiveShellEmbed(banner1=banner)(local_ns=ns)
            else:
                # piped stdin (tests, batch use): the stdlib console
                # reads scripted lines and EOFs out cleanly
                import code
                code.interact(banner=banner, local=ns, exitmsg="")
        except SystemExit:
            pass

    def _write_results(self):
        if not self.args.result_file:
            return
        results = self.workflow.gather_results()
        with open(self.args.result_file, "w") as f:
            json.dump(results, f, indent=2, default=str)
        self.info("wrote results to %s", self.args.result_file)

    # -- dispatch ----------------------------------------------------------

    def _run_regular(self, module):
        # periodic HBM/RSS gauges (veles_hbm_*_bytes, host RSS) for
        # the dashboard's memory panel; VELES_MEMORY_SAMPLE_S=0 off.
        # Only here: the sampler asks JAX for its devices, and on the
        # --optimize/--ensemble branches the chip belongs to the
        # evaluator processes, not to this parent
        from veles_tpu.telemetry import profiler
        profiler.start_memory_sampler()
        run_fn = getattr(module, "run", None)
        if callable(run_fn):
            run_fn(self._load, self._main)
        else:
            WorkflowClass = self._find_workflow_class(module)
            self._load(WorkflowClass)
            self._main()
        return self.EXIT_SUCCESS

    def _run_optimize(self, module):
        from veles_tpu.genetics import GeneticsOptimizer
        gens, _, pop = self.args.optimize.partition(":")
        optimizer = GeneticsOptimizer(
            workflow_file=self.args.workflow,
            config_file=self.args.config,
            generations=int(gens or 10),
            population_size=int(pop or 20),
            result_file=self.args.result_file)
        optimizer.run()
        return self.EXIT_SUCCESS

    def _run_ensemble_train(self, module):
        from veles_tpu.ensemble import EnsembleTrainer
        n, _, ratio = self.args.ensemble_train.partition(":")
        trainer = EnsembleTrainer(
            workflow_file=self.args.workflow,
            config_file=self.args.config,
            size=int(n), train_ratio=float(ratio or 0.8),
            result_file=self.args.result_file or "ensemble.json")
        trainer.run()
        return self.EXIT_SUCCESS

    def _run_ensemble_test(self, module):
        from veles_tpu.ensemble import EnsembleTester
        tester = EnsembleTester(
            workflow_file=self.args.workflow,
            config_file=self.args.config,
            results_file=self.args.ensemble_test,
            result_file=self.args.result_file or "ensemble_test.json")
        tester.run()
        return self.EXIT_SUCCESS

    def run(self, argv=None):
        if argv is None:
            argv = sys.argv[1:]
        if argv and argv[0] == "serve":
            # the serving engine is its own process shape (no Launcher,
            # no workflow run loop) with its own flags — dispatch before
            # the training parser rejects them
            from veles_tpu.serving.frontend import main as serve_main
            return serve_main(argv[1:])
        if argv and argv[0] == "sched":
            # same for the gang scheduler's serve/submit/status surface
            from veles_tpu.sched.cli import sched_main
            return sched_main(argv[1:])
        parser = self.init_parser()
        # intermixed: bare k=v override positionals legally FOLLOW
        # options (the ensemble/genetics evaluators build argv that
        # way), which plain parse_args rejects once the optional
        # arguments have consumed the scan position
        self.args = parser.parse_intermixed_args(argv)
        self._ran = False
        self._run_error = None
        if self.args.version:
            from veles_tpu import __version__
            print(__version__)
            return self.EXIT_SUCCESS
        setup_logging(getattr(logging, self.args.verbosity.upper()))
        if not self.args.workflow:
            parser.print_usage()
            return self.EXIT_FAILURE
        # any bare k=v positionals may have landed in config/overrides
        overrides = list(self.args.overrides)
        if self.args.config and "=" in self.args.config:
            overrides.insert(0, self.args.config)
            self.args.config = None
        if self.args.config is None:
            guess = os.path.splitext(self.args.workflow)[0] + "_config.py"
            self.args.config = guess if os.path.exists(guess) else None

        if getattr(self.args, "precision", None):
            from veles_tpu.nn.precision import set_policy
            set_policy(self.args.precision)
        if getattr(self.args, "jax_coordinator", None) and \
                not getattr(self.args, "jax_processes", None):
            # a coordinator with no process count would leave THIS host
            # standalone while its peers block at the coordinator
            raise SystemExit(
                "--jax-coordinator requires --jax-processes (and "
                "--jax-process-id) on every host")
        if getattr(self.args, "jax_processes", None):
            # multi-host pod: join the JAX runtime BEFORE anything
            # touches a device; every host then sees the global mesh
            # and the parallel trainers shard across DCN+ICI
            from veles_tpu.parallel.mesh import init_multihost
            init_multihost(self.args.jax_coordinator,
                           self.args.jax_processes,
                           self.args.jax_process_id)
        self._seed_random(self.args.seed)
        module = self._load_model(self.args.workflow)
        self._apply_config(self.args.config)
        self._override_config(overrides)
        if self.args.dump_config:
            root.print_()

        if self.args.trace_out:
            from veles_tpu.telemetry import tracing
            tracing.enable()
            # the exit-dump merge is for the processes of ONE run
            # (master + slaves); a file left by a previous run must
            # not leak its stale timeline into this one
            try:
                os.remove(self.args.trace_out)
            except OSError:
                pass
        try:
            if self.args.optimize:
                return self._run_optimize(module)
            if self.args.ensemble_train:
                return self._run_ensemble_train(module)
            if self.args.ensemble_test:
                return self._run_ensemble_test(module)
            return self._run_regular(module)
        except KeyboardInterrupt:
            self.warning("interrupted")
            return self.EXIT_FAILURE
        finally:
            if self.args.trace_out:
                from veles_tpu.telemetry import tracing
                n = tracing.get_buffer().dump(
                    self.args.trace_out,
                    process_name=getattr(getattr(self, "launcher", None),
                                         "mode", None) or "veles_tpu")
                self.info("wrote %d trace events to %s", n,
                          self.args.trace_out)
                # per-buffer HBM attribution rides along (pprof gzip;
                # `pprof -http : FILE` or pprof.me to inspect)
                from veles_tpu.telemetry import profiler
                if profiler.dump_memory_profile(
                        self.args.trace_out + ".memprof"):
                    self.info("wrote device memory profile to "
                              "%s.memprof", self.args.trace_out)


def main(argv=None):
    return Main().run(argv)


if __name__ == "__main__":
    sys.exit(main())
