from bench.kernels import traced_step_bytes


def test_traced_step_bytes_by_hand():
    # 5 edges: src, dst int32 + valid bool read = 9 B each, edge activity
    # bool written = 1 B each; 3 vertices + sentinel: props f32 + active bool
    # read (5 B) and written (5 B).
    assert traced_step_bytes(3, 5, weighted=False) == 5 * 9 + 5 * 1 + 4 * 5 + 4 * 5
    # a weighted graph also reads a float32 weight per edge
    assert traced_step_bytes(3, 5, weighted=True) == 90 + 5 * 4
