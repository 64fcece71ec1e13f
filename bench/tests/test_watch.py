"""The window's diagnostics: a stretch with no answer names what the
serving threads were doing."""
import threading
import time

import loadgen
import watch


def test_a_stall_records_the_serving_threads_stacks():
    rec = loadgen.Records(2)
    release = threading.Event()

    def stuck():
        release.wait(5.0)

    worker = threading.Thread(target=stuck, name="serve-worker0")
    worker.start()
    w = watch.StallWatch(rec).start()
    rec.window = (loadgen.clock(), loadgen.clock() + 5.0)
    rec.begin(0, rec.window[0])
    time.sleep(watch.STALL_S + 0.3)
    w.stop()
    release.set()
    worker.join()
    notes = w.notes(rec.window[0])
    assert notes[0].startswith("longest oversleep")
    assert notes[1].startswith("stall: no answer for")
    assert "1 request(s) out" in notes[1]
    assert any("serve-worker0" in n and "stuck" in n for n in notes[2:])


def test_no_stall_while_answers_come():
    rec = loadgen.Records(40)
    w = watch.StallWatch(rec).start()
    rec.window = (loadgen.clock(), loadgen.clock() + 1.0)
    for i in range(40):
        rec.begin(i, loadgen.clock())
        time.sleep(0.03)
        rec.finish(i, None)
    w.stop()
    assert not w.stall


def test_the_second_process_ticker_stops_and_reports():
    host = watch.HostWatch()
    time.sleep(0.2)
    t0 = loadgen.clock()
    host.stop()
    assert host._proc.returncode == 0
    notes = host.notes((t0 - 1.0, t0))
    assert notes[0].startswith("a second process's 10 ms tick overslept")
    assert all(n.startswith("second process: oversleep") for n in notes[1:])
