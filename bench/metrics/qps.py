"""qps (queries/s): OK answers that reached the client inside the window,
over the window's length. Host clock."""
import readings


def read(run):
    rec = run.records
    t0, t1 = rec.window
    done = sum(1 for i in rec.issued
               if readings.ok(run, i) and t0 <= rec.done[i] <= t1)
    return done / (t1 - t0)
