"""Bridge from the prediction stack to the DriftDetector.

The dry-run predicts step time offline (AOT lower → compile →
``cost_analysis`` + HLO collective parse → :class:`Roofline`); this module
runs the *same* pipeline against the live step function so the
:class:`~repro.obs.drift.DriftDetector` has a prediction for the exact
program the run executes — not a nearby dry-run cell.  With a TuningDB the
collective term is priced at the record's *measured* α/bandwidth
(:meth:`LatencyModel.from_record`), and the record's fit residuals ride
along as the static ``model_error`` baseline the live gauge is compared
against in the report.

Imported lazily by the Trainer (this module pulls jax + the roofline; the
rest of ``repro.obs`` stays stdlib-only).
"""

from __future__ import annotations

from repro.comm.plan import LatencyModel, device_peaks
from repro.launch.roofline import Roofline, collective_wire_bytes


def predict_step_time(step_fn, example_args, *, mesh,
                      overlap_fraction: float = 0.0,
                      latency: LatencyModel | None = None) -> dict:
    """AOT-lower ``step_fn(*example_args)`` and price it.

    Returns the roofline terms plus ``t_step_s`` (the overlap-honest bound
    the drift detector compares measured steps against).  The peaks are
    those of the mesh's devices from :data:`repro.comm.plan.DEVICE_PEAKS`;
    a device without published peaks raises.  ``latency`` replaces the α/β constants with measured
    ones (a tuning-DB record); ``overlap_fraction`` is the executed
    CommSchedule's.
    """
    kind = mesh.devices.flat[0].device_kind
    peaks = device_peaks(kind)
    with mesh:
        lowered = step_fn.lower(*example_args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    stats = collective_wire_bytes(compiled.as_text())
    roof_kw = dict(
        peak_flops=peaks.bf16_flops,
        hbm_bandwidth=peaks.hbm_bandwidth,
        flops_per_device=float(ca.get("flops", 0.0)),
        hbm_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=stats.wire_bytes,
        overlap_fraction=overlap_fraction,
        messages_per_device=stats.messages,
    )
    roof = (Roofline.from_latency(latency, **roof_kw) if latency is not None
            else Roofline(link_bandwidth=peaks.link_bandwidth, **roof_kw))
    return {
        "t_step_s": roof.bound_time_overlapped,
        "t_compute_s": roof.t_compute,
        "t_memory_s": roof.t_memory,
        "t_collective_s": roof.t_collective,
        "t_exposed_collective_s": roof.t_exposed_collective,
        "bottleneck": roof.bottleneck,
        "overlap_fraction": overlap_fraction,
        "wire_bytes_per_device": stats.wire_bytes,
        "messages_per_device": stats.messages,
        "alpha_s": roof.alpha_s,
        "link_bandwidth": roof.link_bandwidth,
        "device_kind": kind,
        "source": "tuned" if latency is not None else "roofline",
    }


def tuned_latency(db_path: str, *, transport: str | None = None,
                  mesh_label: str | None = None, channels: int | None = None,
                  page_bytes: int | None = None, arch: str | None = None
                  ) -> tuple[LatencyModel, dict, str] | None:
    """Resolve a :class:`LatencyModel` (plus its fit-residual summary and
    DB key) from a tuning DB for the active comm config; ``None`` when no
    record matches — the caller falls back to the hardcoded constants."""
    from repro.tune.db import TuningDB, model_error_summary

    db = TuningDB.load(db_path)
    got = db.lookup(transport=transport, arch=arch, mesh=mesh_label,
                    channels=channels, page_bytes=page_bytes)
    if got is None:
        return None
    key, rec = got
    return LatencyModel.from_record(rec), model_error_summary(rec), key
