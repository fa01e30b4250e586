"""queue_wait_p95_ms (ms), scheduler layer: 95th percentile of admitted
minus arrival over the requests due in the window, from the engine's
own request marks (``ServeMetrics.records``); engine time is window time
(``harness.driver.WindowClock``)."""

from harness.stats import percentile


def read(run):
    v = [r.queue_wait for r in run.engine_metrics.records.values()
         if r.queue_wait is not None]
    return 1e3 * percentile(v, 95) if v else None
