"""`pio-tpu` console — the CLI lifecycle entry point.

Parity target: «tools/.../tools/console/Console.scala :: Console.main»
(SURVEY.md §2.3 [U]), verb-for-verb: app, accesskey, eventserver, build,
train, deploy, eval, import, export, batchpredict, status, version,
dashboard. Verbs are registered here and wired to their subsystems as the
layers land; unwired verbs exit with a clear message rather than a stack
trace.
"""

from __future__ import annotations

import argparse
import json
import sys

import predictionio_tpu


def cmd_version(args) -> int:
    print(predictionio_tpu.__version__)
    return 0


def cmd_status(args) -> int:
    """Storage connectivity health check (`pio status` [U]) + which
    native fast paths this host can run."""
    from predictionio_tpu.storage import Storage

    results = Storage.get().verify_all_data_objects()
    for name, ok in results.items():
        print(f"  {name}: {'OK' if ok else 'FAILED'}")
    ok = all(results.values())
    print("Storage status: " + ("all OK" if ok else "FAILURES detected"))
    # native tier: informational, never a failure, never a compile —
    # every native path has a bit-identical Python fallback and the
    # status reads cached state only (ADVICE: a health check must not
    # block on g++ or die on a missing source tree)
    from predictionio_tpu import native

    print("Native fast paths (scan/bucketize/import/export/aggregate): "
          + native.native_status())
    return 0 if ok else 1


def cmd_app(args) -> int:
    from predictionio_tpu.tools.command_client import CommandClient

    client = CommandClient()
    if args.app_command == "new":
        created = client.create_app(args.name, args.description or "")
        if created is None:
            print(f"App {args.name!r} already exists.", file=sys.stderr)
            return 1
        app_id, key = created
        print(f"Created a new app:")
        print(f"      Name: {args.name}")
        print(f"        ID: {app_id}")
        print(f"Access Key: {key}")
        return 0
    if args.app_command == "list":
        for info in client.list_apps():
            key_str = info.access_keys[0] if info.access_keys else "(none)"
            print(f"  {info.id} {info.name} key={key_str}")
        return 0
    if args.app_command == "delete":
        if not client.delete_app(args.name):
            print(f"App {args.name!r} does not exist.", file=sys.stderr)
            return 1
        print(f"Deleted app {args.name}.")
        return 0
    if args.app_command == "data-delete":
        if not client.delete_app_data(args.name):
            print(f"App {args.name!r} does not exist.", file=sys.stderr)
            return 1
        print(f"Deleted all events of app {args.name}.")
        return 0
    if args.app_command == "channel-new":
        try:
            cid = client.create_channel(args.name, args.channel)
        except (KeyError, ValueError) as e:
            msg = e.args[0] if e.args else str(e)
            print(msg, file=sys.stderr)
            return 1
        print(f"Created channel {args.channel} (id={cid}) for app {args.name}.")
        return 0
    print(f"Unknown app command {args.app_command!r}", file=sys.stderr)
    return 1


def cmd_accesskey(args) -> int:
    from predictionio_tpu.storage import AccessKey, Storage

    storage = Storage.get()
    keys = storage.meta_access_keys()
    if args.ak_command == "new":
        app = storage.meta_apps().get_by_name(args.app_name)
        if app is None:
            print(f"App {args.app_name!r} does not exist.", file=sys.stderr)
            return 1
        key = AccessKey.generate(app.id, events=args.event or [])
        keys.insert(key)
        print(f"Created new access key: {key.key}")
        return 0
    if args.ak_command == "list":
        app = storage.meta_apps().get_by_name(args.app_name)
        if app is None:
            print(f"App {args.app_name!r} does not exist.", file=sys.stderr)
            return 1
        for k in keys.get_by_app_id(app.id):
            print(f"  {k.key} events={k.events or 'all'}")
        return 0
    if args.ak_command == "delete":
        ok = keys.delete(args.key)
        print("Deleted." if ok else "No such key.")
        return 0 if ok else 1
    return 1


def cmd_eventserver(args) -> int:
    from predictionio_tpu.data.api import EventServer, EventServerConfig

    config = EventServerConfig(ip=args.ip, port=args.port, stats=args.stats)
    return _run_service(
        lambda: EventServer(config),
        f"Event Server (stats={'on' if args.stats else 'off'})",
        args.ip, args.port,
    )


def cmd_build(args) -> int:
    """`pio build` [U]. There is no sbt: building = validating that the
    engine.json parses, the factory resolves, and params extract cleanly."""
    from predictionio_tpu.workflow.workflow_utils import (
        extract_engine_params,
        get_engine,
        read_engine_json,
    )

    try:
        variant = read_engine_json(args.engine_json)
        engine = get_engine(variant.engine_factory)
        extract_engine_params(engine, variant)
    except Exception as e:
        print(f"Engine build failed: {e}", file=sys.stderr)
        return 1
    print(f"Engine {variant.id!r} ({variant.engine_factory}) is ready for training.")
    return 0


def cmd_train(args) -> int:
    from predictionio_tpu.workflow.create_workflow import run_train

    try:
        instance = run_train(
            engine_json=args.engine_json,
            engine_version=args.engine_version,
            batch=args.batch,
            seed=args.seed,
            mesh=args.mesh,
            skip_sanity_check=args.skip_sanity_check,
            verbose=args.verbose,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            profile_dir=args.profile_dir,
            metrics_file=args.metrics_file,
            debug_nans=args.debug_nans,
            check_asserts=args.check_asserts,
        )
    except FileNotFoundError as e:
        print(f"Cannot read engine variant: {e}", file=sys.stderr)
        return 1
    except (ImportError, AttributeError, ValueError, TypeError, KeyError) as e:
        print(f"Training failed: {e}", file=sys.stderr)
        return 1
    print(f"Training completed. Engine instance ID: {instance.id}")
    return 0


def cmd_eval(args) -> int:
    from predictionio_tpu.workflow.create_workflow import run_evaluation

    try:
        instance, result = run_evaluation(
            evaluation_class=args.evaluation_class,
            generator_class=args.generator_class,
            batch=args.batch,
            seed=args.seed,
            mesh=args.mesh,
            verbose=args.verbose,
        )
    except (ImportError, AttributeError, ValueError, TypeError) as e:
        print(f"Evaluation failed: {e}", file=sys.stderr)
        return 1
    print(result.summary())
    print(f"Evaluation completed. Instance ID: {instance.id}")
    return 0


def cmd_deploy(args) -> int:
    from predictionio_tpu.workflow.create_server import (
        PredictionServer,
        ServerConfig,
    )

    engine_id, engine_variant = args.engine_id, args.engine_variant
    if args.engine_json and not (engine_id and engine_variant):
        # convenience: take the engine id/variant from engine.json, like
        # the reference console resolving the manifest in the engine dir
        import os

        if os.path.exists(args.engine_json):
            from predictionio_tpu.workflow.workflow_utils import read_engine_json

            try:
                vid = read_engine_json(args.engine_json).id
            except (ValueError, json.JSONDecodeError) as e:
                print(f"Cannot parse {args.engine_json}: {e} "
                      "(pass --engine-id/--engine-variant to skip it)",
                      file=sys.stderr)
                return 1
            engine_id = engine_id or vid
            engine_variant = engine_variant or vid
    engine_id = engine_id or "default"
    engine_variant = engine_variant or "default"
    config = ServerConfig(
        ip=args.ip,
        port=args.port,
        engine_id=engine_id,
        engine_version=args.engine_version,
        engine_variant=engine_variant,
    )
    min_workers = getattr(args, "min_workers", 0) or 0
    max_workers = getattr(args, "max_workers", 0) or 0
    if getattr(args, "workers", 1) > 1 or min_workers or max_workers:
        # pre-fork BEFORE any storage/jax/model state exists in this
        # process — each worker loads its own (runtime/supervisor.py)
        from predictionio_tpu.runtime.supervisor import (
            Supervisor, SupervisorConfig,
        )

        sup_cfg = SupervisorConfig.from_env()
        # CLI bounds override the env posture (the flags are the
        # operator's on-call lever; env is the deploy manifest's)
        if min_workers:
            sup_cfg.min_workers = min_workers
        if max_workers:
            sup_cfg.max_workers = max_workers
        if (sup_cfg.min_workers > 0 and sup_cfg.max_workers > 0
                and sup_cfg.min_workers > sup_cfg.max_workers):
            print(f"--min-workers {sup_cfg.min_workers} exceeds "
                  f"--max-workers {sup_cfg.max_workers}", file=sys.stderr)
            return 1
        n = max(args.workers, 1)
        if sup_cfg.min_workers > 0:
            n = max(n, sup_cfg.min_workers)
        if sup_cfg.max_workers > 0:
            n = min(n, sup_cfg.max_workers)
        return Supervisor(config, n, cfg=sup_cfg).run()
    try:
        server = PredictionServer(config)
    except (RuntimeError, ImportError, AttributeError, ValueError, TypeError,
            KeyError) as e:
        print(f"Deploy failed: {e}", file=sys.stderr)
        return 1
    except OSError as e:
        print(f"Cannot bind {args.ip}:{args.port}: {e.strerror or e}", file=sys.stderr)
        return 1
    print(f"Engine instance {server.instance_id} deployed on "
          f"{args.ip}:{server.port}", flush=True)
    return _serve_until_signal(server)


def cmd_batchpredict(args) -> int:
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    try:
        n = run_batch_predict(
            input_path=args.input,
            output_path=args.output,
            engine_id=args.engine_id,
            engine_version=args.engine_version,
            engine_variant=args.engine_variant,
        )
    except (RuntimeError, FileNotFoundError, ValueError, TypeError, KeyError,
            ImportError, AttributeError) as e:
        print(f"Batch predict failed: {e}", file=sys.stderr)
        return 1
    print(f"Batch predict completed: {n} queries → {args.output}")
    return 0


def cmd_import(args) -> int:
    from predictionio_tpu.tools.transfer import file_to_events

    try:
        imported, skipped = file_to_events(args.input, args.appname, args.channel)
    except (ValueError, OSError, RuntimeError) as e:
        print(f"Import failed: {e}", file=sys.stderr)
        return 1
    print(f"Imported {imported} events" +
          (f" ({skipped} invalid lines skipped)" if skipped else "") + ".")
    return 0


def cmd_export(args) -> int:
    from predictionio_tpu.tools.transfer import events_to_file

    try:
        n = events_to_file(args.output, args.appname, args.channel)
    except (ValueError, OSError) as e:
        print(f"Export failed: {e}", file=sys.stderr)
        return 1
    print(f"Exported {n} events to {args.output}.")
    return 0


def _serve_until_signal(server) -> int:
    """Block in serve_forever until SIGINT/SIGTERM, then shut down
    gracefully: stop accepting, close storage (checkpoints SQLite WAL),
    flush logs — the supervised-shutdown contract the reference gets from
    its Akka actor system."""
    import signal

    def _terminate(signum, frame):
        raise KeyboardInterrupt

    prev = signal.signal(signal.SIGTERM, _terminate)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        signal.signal(signal.SIGTERM, prev)
        server.shutdown()
        from predictionio_tpu.storage import Storage

        Storage.get().close()
        sys.stdout.flush()
    return 0


def _run_service(make_server, what: str, ip: str, port: int) -> int:
    try:
        server = make_server()
    except OSError as e:
        print(f"Cannot bind {ip}:{port}: {e.strerror or e}", file=sys.stderr)
        return 1
    print(f"{what} listening on {ip}:{server.port}", flush=True)
    return _serve_until_signal(server)


def cmd_dashboard(args) -> int:
    from predictionio_tpu.tools.dashboard import Dashboard

    return _run_service(lambda: Dashboard(ip=args.ip, port=args.port),
                        "Dashboard", args.ip, args.port)


def cmd_template(args) -> int:
    """`pio template {list,get}` (0.9.x «console/Template.scala» [U]).
    Templates are built-in packages; `get` scaffolds a user dir."""
    from predictionio_tpu.templates.registry import (
        BUILTIN_TEMPLATES,
        scaffold,
    )

    if args.template_command == "list":
        for name, info in sorted(BUILTIN_TEMPLATES.items()):
            print(f"  {name:20s} {info.description}")
        return 0
    if args.template_command == "get":
        try:
            directory = scaffold(args.name, args.directory,
                                 app_name=args.app_name)
        except (KeyError, FileExistsError) as e:
            print(e.args[0] if e.args else str(e), file=sys.stderr)
            return 1
        print(f"Engine template {args.name!r} created at {directory}")
        print("Edit engine.json, then: pio-tpu build && pio-tpu train "
              "&& pio-tpu deploy")
        return 0
    return 1


def cmd_new(args) -> int:
    """`pio new <dir>`: scaffold a template (shorthand for template get)."""
    args.template_command = "get"
    args.name = args.template
    return cmd_template(args)


def cmd_run(args) -> int:
    """`pio run <module[:callable]>` («tools/Runner.scala :: runOnSpark»
    [U]): run a user entry point in-process (the rebuild has no
    spark-submit; in-process IS the deployment model). The multi-host
    bootstrap runs first, as it does for `train`."""
    import importlib

    from predictionio_tpu.parallel.distributed import initialize_from_env

    initialize_from_env()  # no-op unless PIO_COORDINATOR_* env is set
    target = args.target
    module_name, _, attr = target.partition(":")
    try:
        module = importlib.import_module(module_name)
    except ImportError as e:
        print(f"Cannot import {module_name!r}: {e}", file=sys.stderr)
        return 1
    if attr:
        fn = getattr(module, attr, None)
        if fn is None:
            print(f"{module_name} has no attribute {attr!r}", file=sys.stderr)
            return 1
        result = fn(*args.args)
    elif hasattr(module, "main"):
        result = module.main(args.args)
    else:
        print(f"{module_name} has no main(); use {module_name}:<callable>",
              file=sys.stderr)
        return 1
    return result if isinstance(result, int) else 0


def cmd_upgrade(args) -> int:
    """`pio upgrade` [U]. Upstream migrated storage between versions; the
    rebuild's storage schema is version-stable so far, so this verifies
    connectivity and reports the version."""
    import predictionio_tpu
    from predictionio_tpu.storage import Storage

    results = Storage.get().verify_all_data_objects()
    ok = all(results.values())
    print(f"predictionio-tpu {predictionio_tpu.__version__}: storage "
          + ("is up to date." if ok else "has FAILURES — run `pio-tpu status`."))
    return 0 if ok else 1


def cmd_adminserver(args) -> int:
    from predictionio_tpu.tools.admin import AdminServer

    return _run_service(lambda: AdminServer(ip=args.ip, port=args.port),
                        "Admin server", args.ip, args.port)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="pio-tpu", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    sub.add_parser("version").set_defaults(func=cmd_version)
    sub.add_parser("status").set_defaults(func=cmd_status)

    app = sub.add_parser("app")
    app_sub = app.add_subparsers(dest="app_command", required=True)
    app_new = app_sub.add_parser("new")
    app_new.add_argument("name")
    app_new.add_argument("--description", default="")
    app_sub.add_parser("list")
    app_del = app_sub.add_parser("delete")
    app_del.add_argument("name")
    app_dd = app_sub.add_parser("data-delete")
    app_dd.add_argument("name")
    app_ch = app_sub.add_parser("channel-new")
    app_ch.add_argument("name")
    app_ch.add_argument("channel")
    app.set_defaults(func=cmd_app)

    ak = sub.add_parser("accesskey")
    ak_sub = ak.add_subparsers(dest="ak_command", required=True)
    ak_new = ak_sub.add_parser("new")
    ak_new.add_argument("app_name")
    ak_new.add_argument("--event", action="append")
    ak_list = ak_sub.add_parser("list")
    ak_list.add_argument("app_name")
    ak_del = ak_sub.add_parser("delete")
    ak_del.add_argument("key")
    ak.set_defaults(func=cmd_accesskey)

    es = sub.add_parser("eventserver")
    es.add_argument("--ip", default="0.0.0.0")
    es.add_argument("--port", type=int, default=7070)
    es.add_argument("--stats", action="store_true")
    es.set_defaults(func=cmd_eventserver)

    build = sub.add_parser("build")
    build.add_argument("--engine-json", default="engine.json")
    build.set_defaults(func=cmd_build)

    def add_run_args(sp):
        sp.add_argument("--batch", default="")
        sp.add_argument("--seed", type=int, default=0)
        sp.add_argument("--mesh", default=None,
                        help="device mesh spec, e.g. data=4,model=2")
        sp.add_argument("--verbose", type=int, default=0)

    train = sub.add_parser("train")
    train.add_argument("--engine-json", default="engine.json",
                       help="engine variant file (the reference's --variant)")
    train.add_argument("--engine-version", default="1")
    add_run_args(train)
    train.add_argument("--skip-sanity-check", action="store_true")
    train.add_argument("--checkpoint-dir", default=None,
                       help="checkpoint trainer state here every "
                            "--checkpoint-every steps of each "
                            "algorithm's unit (ALS: epochs; W2V/LogReg: "
                            "scan iterations); re-running train resumes "
                            "from the latest step")
    train.add_argument("--checkpoint-every", type=int, default=None,
                       help="default: per-algorithm (ALS every epoch; "
                            "step-loop trainers ~10 saves per run)")
    train.add_argument("--profile-dir", default=None,
                       help="capture a jax.profiler trace here "
                            "(TensorBoard/Perfetto layout)")
    train.add_argument("--metrics-file", default=None,
                       help="append per-epoch metrics as JSON lines here")
    train.add_argument("--debug-nans", action="store_true",
                       help="recompile with NaN detection (slow)")
    train.add_argument("--check-asserts", action="store_true",
                       help="checkify assert mode: float/user checks inside "
                            "jitted train loops (slow; SURVEY.md §5)")
    train.set_defaults(func=cmd_train)

    ev = sub.add_parser("eval")
    ev.add_argument("evaluation_class")
    ev.add_argument("generator_class", nargs="?", default=None)
    add_run_args(ev)
    ev.set_defaults(func=cmd_eval)

    deploy = sub.add_parser("deploy")
    deploy.add_argument("--ip", default="0.0.0.0")
    deploy.add_argument("--port", type=int, default=8000)
    deploy.add_argument("--workers", type=int, default=1,
                        help="N pre-forked serving processes sharing the "
                             "port via SO_REUSEPORT (kernel-balanced; "
                             "/reload and /stop fan out to all); each "
                             "worker is a full process with its own GIL, "
                             "so qps scales with cores")
    deploy.add_argument("--min-workers", type=int, default=0,
                        help="autoscaler floor: the supervisor never "
                             "shrinks the pool below this (implies pool "
                             "mode; default: the --workers count)")
    deploy.add_argument("--max-workers", type=int, default=0,
                        help="autoscaler ceiling: the supervisor grows "
                             "the pool up to this under sustained queue "
                             "pressure or SLO burn (implies pool mode; "
                             "default: the --workers count)")
    deploy.add_argument("--engine-id", default=None)
    deploy.add_argument("--engine-version", default="1")
    deploy.add_argument("--engine-variant", default=None)
    deploy.add_argument("--engine-json", default="engine.json")
    deploy.set_defaults(func=cmd_deploy)

    bp = sub.add_parser("batchpredict")
    bp.add_argument("--input", required=True)
    bp.add_argument("--output", required=True)
    bp.add_argument("--engine-id", default="default")
    bp.add_argument("--engine-version", default="1")
    bp.add_argument("--engine-variant", default="default")
    bp.set_defaults(func=cmd_batchpredict)

    imp = sub.add_parser("import")
    imp.add_argument("--appname", required=True)
    imp.add_argument("--input", required=True)
    imp.add_argument("--channel", default=None)
    imp.set_defaults(func=cmd_import)

    exp = sub.add_parser("export")
    exp.add_argument("--appname", required=True)
    exp.add_argument("--output", required=True)
    exp.add_argument("--channel", default=None)
    exp.set_defaults(func=cmd_export)

    dash = sub.add_parser("dashboard")
    dash.add_argument("--ip", default="0.0.0.0")
    dash.add_argument("--port", type=int, default=9000)
    dash.set_defaults(func=cmd_dashboard)

    adm = sub.add_parser("adminserver")
    adm.add_argument("--ip", default="0.0.0.0")
    adm.add_argument("--port", type=int, default=7071)
    adm.set_defaults(func=cmd_adminserver)

    tpl = sub.add_parser("template")
    tpl_sub = tpl.add_subparsers(dest="template_command", required=True)
    tpl_sub.add_parser("list")
    tpl_get = tpl_sub.add_parser("get")
    tpl_get.add_argument("name")
    tpl_get.add_argument("directory")
    tpl_get.add_argument("--app-name", default=None)
    tpl.set_defaults(func=cmd_template)

    new = sub.add_parser("new")
    new.add_argument("directory")
    new.add_argument("--template", default="recommendation")
    new.add_argument("--app-name", default=None)
    new.set_defaults(func=cmd_new)

    run = sub.add_parser("run")
    run.add_argument("target", help="module or module:callable to execute")
    run.add_argument("args", nargs=argparse.REMAINDER,
                     help="arguments forwarded verbatim to the target")
    run.set_defaults(func=cmd_run)

    sub.add_parser("upgrade").set_defaults(func=cmd_upgrade)

    return p


def main(argv=None) -> int:
    import logging
    import os

    from predictionio_tpu.utils import compile_cache

    # before any verb imports jax; forked/spawned workers inherit it
    compile_cache.configure()
    args = build_parser().parse_args(argv)
    # Wire log levels like the reference's `pio --verbose` / log4j.properties
    # (SURVEY.md §5): WARNING by default, INFO at --verbose 1, DEBUG at ≥2;
    # PIO_LOG_LEVEL overrides (e.g. PIO_LOG_LEVEL=INFO for services, which
    # have no --verbose flag).
    verbose = getattr(args, "verbose", 0)
    name = os.environ.get(
        "PIO_LOG_LEVEL",
        "DEBUG" if verbose >= 2 else "INFO" if verbose == 1 else "WARNING"
    ).upper()
    levels = {"CRITICAL": logging.CRITICAL, "FATAL": logging.CRITICAL,
              "ERROR": logging.ERROR, "WARNING": logging.WARNING,
              "WARN": logging.WARNING, "INFO": logging.INFO,
              "DEBUG": logging.DEBUG, "NOTSET": logging.NOTSET}
    level = int(name) if name.isdigit() else levels.get(name, logging.WARNING)
    # Every record carries the active request's trace id (or "-") so one
    # X-PIO-Trace-Id can be grepped across event-server, prediction-server,
    # and storage log lines. Must install before basicConfig snapshots a
    # formatter.
    from predictionio_tpu.telemetry.tracing import install_log_record_factory

    install_log_record_factory()
    logging.basicConfig(
        level=level,
        format="%(asctime)s %(levelname)s %(name)s [%(trace_id)s]: %(message)s")
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
