"""Shared ``--metrics`` / ``--trace-out`` plumbing for the launch CLIs.

Both drivers expose the same two flags: ``--metrics [PATH]`` enables
:mod:`repro.obs` and dumps the Prometheus-text metrics at exit (to PATH, or
stdout when the flag is bare), ``--trace-out DIR`` additionally profiles the
run with the JAX profiler into DIR: one timeline that holds the program's
spans and the device's ops, as ``.xplane.pb`` and as a Perfetto trace
(``perfetto_trace.json.gz``, loadable in https://ui.perfetto.dev). Usage::

    add_obs_args(ap)
    args = ap.parse_args(argv)
    observing = obs_begin(args)
    try:
        ...
    finally:
        obs_end(args, observing)
"""
from __future__ import annotations

import argparse

import jax

from repro import obs


def add_obs_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--metrics", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="enable repro.obs and dump Prometheus-text metrics "
                         "at exit (to PATH, or stdout when bare)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="enable repro.obs and profile the run into DIR: "
                         "program spans and device ops on one timeline, "
                         "with a Perfetto trace")


def obs_begin(args: argparse.Namespace) -> bool:
    """Enable observability when either flag was passed, and start the
    profile for ``--trace-out``; returns whether observing."""
    observing = args.metrics is not None or args.trace_out is not None
    if observing:
        obs.reset()
        obs.enable()
    if args.trace_out is not None:
        # no Python tracer: it would time every Python call of the host loop
        # and slow it many times over, hiding what the spans measure
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(args.trace_out, create_perfetto_trace=True,
                                 profiler_options=opts)
    return observing


def obs_end(args: argparse.Namespace, observing: bool) -> None:
    """Stop the profile, disable observability and write/print the
    requested exports."""
    if not observing:
        return
    if args.trace_out is not None:
        jax.profiler.stop_trace()
        print(f"# wrote profile to {args.trace_out}")
    obs.disable()
    if args.metrics is not None:
        text = obs.to_prometheus()
        if args.metrics:
            with open(args.metrics, "w") as f:
                f.write(text)
            print(f"# wrote metrics to {args.metrics}")
        else:
            print("# --- metrics (prometheus text) ---")
            print(text, end="")
