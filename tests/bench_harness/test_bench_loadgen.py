"""The load generator's window: paced open loop when the traffic states a
rate, as fast as the server reads when it does not."""
import socket
import threading

import pytest

import loadgen

RMAT = {"scale": 20, "a": 0.57, "b": 0.19, "c": 0.19}


def _sink():
    """A loopback server that reads and discards; returns (port, bytes read)."""
    srv = socket.create_server(("127.0.0.1", 0))
    got = [0]

    def serve():
        conn, _ = srv.accept()
        with conn:
            while True:
                b = conn.recv(1 << 20)
                if not b:
                    break
                got[0] += len(b)
        srv.close()

    threading.Thread(target=serve, daemon=True).start()
    return srv.getsockname()[1], got


@pytest.mark.parametrize("rate", [2048, None])
def test_window_paces_by_the_traffic_rate(rate):
    gen = loadgen.Generator({"seed": 3, "chunk_records": 256,
                             "traffic": {"rate": rate, "rmat": RMAT}})
    port, _ = _sink()
    out = gen.window(port, 1.0)
    assert out["records"] == out["chunks"] * 256 == len(out["stamps_s"]) * 256
    if rate is None:
        assert out["records"] > 4 * 2048  # unpaced: far more than the paced rate
    else:
        assert out["records"] == 2048  # chunk i due at i * 256 / 2048 s, i < 8
        assert out["stamps_s"][-1] == pytest.approx(7 * 256 / 2048, abs=0.05)
