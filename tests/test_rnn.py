"""RNN toolkit tests — reference ``tests/python/unittest/test_rnn.py``:
cell unroll shapes, fused-vs-unfused numerical consistency via
pack/unpack_weights, bucketing iterator semantics."""

import numpy as np
import pytest

import mxnet_tpu as mx


def _eval_sym(sym, arg_arrays):
    ex = sym.bind(mx.cpu(), arg_arrays)
    return [o.asnumpy() for o in ex.forward(is_train=False)]


def test_rnn_cell_unroll_shapes():
    cell = mx.rnn.RNNCell(10, prefix="rnn_")
    outputs, states = cell.unroll(3, inputs=mx.sym.Variable("data"),
                                  merge_outputs=True)
    _, outs, _ = outputs.infer_shape(data=(2, 3, 7))
    assert outs == [(2, 3, 10)]
    assert sorted(cell.params._params) == [
        "rnn_h2h_bias", "rnn_h2h_weight", "rnn_i2h_bias", "rnn_i2h_weight"]


def test_lstm_cell_unroll_shapes():
    cell = mx.rnn.LSTMCell(10, prefix="lstm_")
    outputs, states = cell.unroll(3, inputs=mx.sym.Variable("data"),
                                  merge_outputs=True)
    _, outs, _ = outputs.infer_shape(data=(2, 3, 7))
    assert outs == [(2, 3, 10)]
    assert len(states) == 2


def test_gru_cell_unroll_shapes():
    cell = mx.rnn.GRUCell(10, prefix="gru_")
    outputs, _ = cell.unroll(3, inputs=mx.sym.Variable("data"),
                             merge_outputs=True)
    _, outs, _ = outputs.infer_shape(data=(2, 3, 7))
    assert outs == [(2, 3, 10)]


def test_unroll_list_inputs():
    cell = mx.rnn.LSTMCell(10, prefix="lstm_")
    seq = [mx.sym.Variable("t%d" % i) for i in range(3)]
    outputs, _ = cell.unroll(3, inputs=seq, merge_outputs=False)
    assert len(outputs) == 3
    _, outs, _ = outputs[2].infer_shape(t0=(2, 7), t1=(2, 7), t2=(2, 7))
    assert outs == [(2, 10)]


@pytest.mark.parametrize("mode", ["rnn_relu", "rnn_tanh", "lstm", "gru"])
def test_fused_matches_unfused(mode):
    """The lax.scan fused RNN and the per-step unrolled cells must produce
    identical outputs from the same parameter blob (reference
    test_rnn.py consistency checks)."""
    T, N, I, H, L = 4, 3, 5, 6, 2
    fused = mx.rnn.FusedRNNCell(H, num_layers=L, mode=mode, prefix="f_",
                                get_next_state=False)
    data = mx.sym.Variable("data")
    fsym, _ = fused.unroll(T, inputs=data, merge_outputs=True)

    stack = fused.unfuse()
    usym, _ = stack.unroll(T, inputs=data, merge_outputs=True)

    from mxnet_tpu.ops.rnn import rnn_param_size

    rs = np.random.RandomState(0)
    blob = mx.nd.array(rs.uniform(-0.5, 0.5,
                                  rnn_param_size(I, H, L, mode)).astype("f"))
    x = mx.nd.array(rs.randn(N, T, I).astype("f"))

    fout = _eval_sym(fsym, {"data": x, "f_parameters": blob})[0]
    uargs = fused.unpack_weights({"f_parameters": blob})
    uout = _eval_sym(usym, dict(uargs, data=x))[0]
    assert fout.shape == uout.shape == (N, T, H)
    np.testing.assert_allclose(fout, uout, rtol=1e-4, atol=1e-5)


def _numpy_lstm(x, blob, h0, c0, layers, hidden):
    """A float64 LSTM over the RNN op's parameter blob (a layer: ``Wx
    (4H, I)``, ``Wh (4H, H)``, ``bx``, ``bh``; gates ``i, f, g, o``):
    outputs, final states, and by backpropagation through time the
    gradients of ``sum(y) + sum(h_T) + sum(c_T)``."""
    def sig(a):
        return 1.0 / (1.0 + np.exp(-a))

    x, blob, h0, c0 = (a.astype(np.float64) for a in (x, blob, h0, c0))
    T, N, _ = x.shape
    H, off, weights, tapes, cur = hidden, 0, [], [], x
    hT, cT = [], []
    for l in range(layers):
        i_dim = cur.shape[2]
        spec = []
        for shape in ((4 * H, i_dim), (4 * H, H), (4 * H,), (4 * H,)):
            n = int(np.prod(shape))
            spec.append((off, shape))
            off += n
        wx, wh, bx, bh = (blob[o:o + int(np.prod(s))].reshape(s)
                          for o, s in spec)
        weights.append(spec)
        h, c, ys, tape = h0[l], c0[l], [], []
        for t in range(T):
            gates = cur[t] @ wx.T + bx + h @ wh.T + bh
            i, f, g, o = np.split(gates, 4, axis=-1)
            i, f, g, o = sig(i), sig(f), np.tanh(g), sig(o)
            c_new = f * c + i * g
            tape.append((cur[t], h, c, i, f, g, o, c_new))
            h, c = o * np.tanh(c_new), c_new
            ys.append(h)
        tapes.append((wx, wh, tape))
        hT.append(h)
        cT.append(c)
        cur = np.stack(ys)
    y = cur
    # backward: every output, final h and final c carries a cotangent of 1
    dblob = np.zeros_like(blob)
    dh0, dc0 = np.zeros_like(h0), np.zeros_like(c0)
    dys = np.ones_like(y)
    for l in reversed(range(layers)):
        wx, wh, tape = tapes[l]
        dwx, dwh, db = np.zeros_like(wx), np.zeros_like(wh), np.zeros(4 * H)
        dh, dc = np.ones((N, H)), np.ones((N, H))
        dxs = [None] * T
        for t in reversed(range(T)):
            xt, hp, cp, i, f, g, o, c_new = tape[t]
            dh = dh + dys[t]
            tc = np.tanh(c_new)
            dc = dc + dh * o * (1 - tc ** 2)
            dgates = np.concatenate(
                [dc * g * i * (1 - i), dc * cp * f * (1 - f),
                 dc * i * (1 - g ** 2), dh * tc * o * (1 - o)], axis=-1)
            dwx += dgates.T @ xt
            dwh += dgates.T @ hp
            db += dgates.sum(0)
            dxs[t] = dgates @ wx
            dh, dc = dgates @ wh, dc * f
        dh0[l], dc0[l] = dh, dc
        for (o_, s), d in zip(weights[l], (dwx, dwh, db, db)):
            dblob[o_:o_ + int(np.prod(s))] = d.ravel()
        dys = np.stack(dxs)
    return (y, np.stack(hT), np.stack(cT)), \
        {"x": dys, "p": dblob, "hs": dh0, "cs": dc0}


@pytest.mark.parametrize("seq,batch,nin,nh,layers", [
    (7, 4, 6, 8, 2),        # what the fused kernel's parity test ran
    (35, 32, 200, 200, 1),  # PTB's sequence, batch and width
])
def test_lstm_scan_path_matches_numpy(seq, batch, nin, nh, layers):
    """The RNN op's one LSTM path (input projection as one product, the
    recurrence in ``lax.scan``): outputs, final states and every gradient
    against a float64 LSTM written out step by step."""
    from mxnet_tpu.ops.rnn import rnn_param_size

    rs = np.random.RandomState(3)
    psize = rnn_param_size(nin, nh, layers, "lstm", False)
    vals = {"x": rs.randn(seq, batch, nin) * 0.5,
            "p": rs.randn(psize) * (0.2 if nh < 100 else 0.05),
            "hs": rs.randn(layers, batch, nh) * 0.1,
            "cs": rs.randn(layers, batch, nh) * 0.1}
    net = mx.sym.RNN(*(mx.sym.Variable(n) for n in ("x", "p", "hs", "cs")),
                     state_size=nh, num_layers=layers, mode="lstm",
                     state_outputs=True, name="rnn")
    ex = net.simple_bind(mx.cpu(), grad_req="write",
                         **{n: v.shape for n, v in vals.items()})
    for n, v in vals.items():
        ex.arg_dict[n][:] = v.astype(np.float32)
    outs = [o.asnumpy() for o in ex.forward(is_train=True)]
    ex.backward([mx.nd.ones(o.shape) for o in ex.outputs])

    ref_outs, ref_grads = _numpy_lstm(
        *(vals[n].astype(np.float32) for n in ("x", "p", "hs", "cs")),
        layers, nh)
    assert len(outs) == 3
    for got, want in zip(outs, ref_outs):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for n, want in ref_grads.items():
        np.testing.assert_allclose(
            ex.grad_dict[n].asnumpy(), want, rtol=1e-3,
            atol=1e-4 * max(1.0, np.abs(want).max()), err_msg=n)


def test_fused_bidirectional_matches_unfused():
    T, N, I, H = 4, 3, 5, 6
    fused = mx.rnn.FusedRNNCell(H, num_layers=1, mode="lstm", prefix="f_",
                                bidirectional=True)
    data = mx.sym.Variable("data")
    fsym, _ = fused.unroll(T, inputs=data, merge_outputs=True)
    stack = fused.unfuse()
    usym, _ = stack.unroll(T, inputs=data, merge_outputs=True)

    from mxnet_tpu.ops.rnn import rnn_param_size

    rs = np.random.RandomState(1)
    blob = mx.nd.array(rs.uniform(
        -0.5, 0.5, rnn_param_size(I, H, 1, "lstm", True)).astype("f"))
    x = mx.nd.array(rs.randn(N, T, I).astype("f"))
    fout = _eval_sym(fsym, {"data": x, "f_parameters": blob})[0]
    uargs = fused.unpack_weights({"f_parameters": blob})
    uout = _eval_sym(usym, dict(uargs, data=x))[0]
    assert fout.shape == uout.shape == (N, T, 2 * H)
    np.testing.assert_allclose(fout, uout, rtol=1e-4, atol=1e-5)


def test_pack_unpack_roundtrip():
    from mxnet_tpu.ops.rnn import rnn_param_size

    fused = mx.rnn.FusedRNNCell(6, num_layers=2, mode="gru", prefix="f_",
                                bidirectional=True)
    rs = np.random.RandomState(2)
    blob = rs.randn(rnn_param_size(5, 6, 2, "gru", True)).astype("f")
    unpacked = fused.unpack_weights({"f_parameters": mx.nd.array(blob)})
    assert "f_parameters" not in unpacked
    assert "f_l0_i2h_weight" in unpacked and "f_r1_h2h_bias" in unpacked
    packed = fused.pack_weights(unpacked)
    np.testing.assert_allclose(packed["f_parameters"].asnumpy(), blob,
                               rtol=1e-6)


def test_bidirectional_cell_unroll():
    cell = mx.rnn.BidirectionalCell(mx.rnn.LSTMCell(4, prefix="l_"),
                                    mx.rnn.LSTMCell(4, prefix="r_"))
    outputs, states = cell.unroll(3, inputs=mx.sym.Variable("data"),
                                  merge_outputs=True)
    _, outs, _ = outputs.infer_shape(data=(2, 3, 5))
    assert outs == [(2, 3, 8)]
    assert len(states) == 4


def test_zoneout_and_dropout_cells():
    cell = mx.rnn.ZoneoutCell(mx.rnn.RNNCell(4, prefix="z_"),
                              zoneout_outputs=0.3, zoneout_states=0.2)
    outputs, _ = cell.unroll(3, inputs=mx.sym.Variable("data"),
                             merge_outputs=True)
    _, outs, _ = outputs.infer_shape(data=(2, 3, 5))
    assert outs == [(2, 3, 4)]

    stack = mx.rnn.SequentialRNNCell()
    stack.add(mx.rnn.LSTMCell(4, prefix="s0_"))
    stack.add(mx.rnn.DropoutCell(0.5, prefix="d_"))
    stack.add(mx.rnn.LSTMCell(4, prefix="s1_"))
    outputs, _ = stack.unroll(3, inputs=mx.sym.Variable("data"),
                              merge_outputs=True)
    _, outs, _ = outputs.infer_shape(data=(2, 3, 5))
    assert outs == [(2, 3, 4)]


def test_encode_sentences():
    sents = [["the", "cat", "sat"], ["the", "dog"]]
    coded, vocab = mx.rnn.encode_sentences(sents, start_label=1)
    assert len(coded) == 2 and coded[0][0] == coded[1][0] == vocab["the"]


def test_bucket_sentence_iter():
    rs = np.random.RandomState(0)
    sentences = [list(rs.randint(1, 20, size=n))
                 for n in rs.randint(2, 9, size=100)]
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=4, buckets=[4, 8],
                                   invalid_label=0)
    assert it.default_bucket_key == 8
    seen = set()
    for batch in it:
        assert batch.bucket_key in (4, 8)
        assert batch.data[0].shape == (4, batch.bucket_key)
        d = batch.data[0].asnumpy()
        lab = batch.label[0].asnumpy()
        np.testing.assert_array_equal(d[:, 1:], lab[:, :-1])
        seen.add(batch.bucket_key)
    assert seen == {4, 8}


def test_lstm_bucketing_end_to_end():
    """PTB-baseline shape (SURVEY §2.9 config 3): BucketingModule +
    Embedding + stacked LSTM + SoftmaxOutput + Perplexity, tiny scale."""
    vocab = 16
    rs = np.random.RandomState(3)
    sentences = [list(rs.randint(1, vocab, size=n))
                 for n in rs.randint(3, 9, size=64)]
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=4, buckets=[4, 8],
                                   invalid_label=0)

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=vocab, output_dim=8,
                                 name="embed")
        stack = mx.rnn.SequentialRNNCell()
        for i in range(2):
            stack.add(mx.rnn.LSTMCell(num_hidden=8, prefix="lstm_l%d_" % i))
        outputs, _ = stack.unroll(seq_len, inputs=embed, merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, 8))
        pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
        label_f = mx.sym.Reshape(label, shape=(-1,))
        return mx.sym.SoftmaxOutput(pred, label_f, name="softmax"), \
            ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=it.default_bucket_key)
    metric = mx.metric.Perplexity(0)
    mod.fit(it, eval_metric=metric, num_epoch=2,
            optimizer="sgd", optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier())
    name, val = metric.get()
    assert np.isfinite(val) and val < vocab * 2


def test_bucket_iter_time_major():
    sentences = [[1, 2, 3, 4]] * 8
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=4, buckets=[4],
                                   invalid_label=0, layout="TNC")
    batch = next(iter(it))
    assert batch.data[0].shape == (4, 4)
    assert it.provide_data[0].shape == (4, 4)


def test_rnn_checkpoint_roundtrip(tmp_path):
    """save_rnn_checkpoint unpacks fused blobs; load_rnn_checkpoint re-packs
    (reference rnn/rnn.py:15-78)."""
    from mxnet_tpu.ops.rnn import rnn_param_size

    H, L, V = 6, 2, 11
    fused = mx.rnn.FusedRNNCell(H, num_layers=L, mode="lstm", prefix="lstm_")
    data = mx.sym.Variable("data")
    embed = mx.sym.Embedding(data, input_dim=V, output_dim=5, name="embed")
    out, _ = fused.unroll(4, inputs=embed, merge_outputs=True, layout="NTC")
    rs = np.random.RandomState(0)
    blob = rs.randn(rnn_param_size(5, H, L, "lstm", False)).astype("f")
    args = {"lstm_parameters": mx.nd.array(blob),
            "embed_weight": mx.nd.array(rs.randn(V, 5).astype("f"))}
    prefix = str(tmp_path / "ck")
    mx.rnn.save_rnn_checkpoint(fused, prefix, 3, out, args, {})

    sym, arg, aux = mx.rnn.load_rnn_checkpoint(fused, prefix, 3)
    np.testing.assert_allclose(arg["lstm_parameters"].asnumpy(), blob,
                               rtol=1e-6)
    np.testing.assert_allclose(arg["embed_weight"].asnumpy(),
                               args["embed_weight"].asnumpy(), rtol=1e-6)
    # the on-disk dict is unpacked: loadable into the unfused stack as-is
    _, arg_unf, _ = mx.rnn.load_rnn_checkpoint(fused.unfuse(), prefix, 3)
    assert "lstm_l0_i2h_weight" in arg_unf
    assert "lstm_parameters" not in arg_unf


def test_fused_cell_init_attr():
    """FusedRNNCell attaches a FusedRNN __init__ attr so Module.init_params
    can initialize the packed blob (reference rnn_cell.py FusedRNNCell)."""
    fused = mx.rnn.FusedRNNCell(4, num_layers=1, mode="lstm", prefix="q_")
    attrs = fused._parameter.attr_dict().get("q_parameters", {})
    assert "__init__" in attrs
    from mxnet_tpu.initializer import InitDesc
    from mxnet_tpu.ops.rnn import rnn_param_size
    arr = mx.nd.zeros((rnn_param_size(3, 4, 1, "lstm", False),))
    mx.init.Xavier()(InitDesc("q_parameters", attrs), arr)
    v = arr.asnumpy()
    assert np.abs(v).sum() > 0  # weights filled


def test_bucket_iter_empty_bucket():
    """Buckets with no sentences must not crash reset/iteration."""
    sentences = [[1, 2, 3]] * 8  # only the len-4 bucket is populated
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=4,
                                   buckets=[4, 10, 20], invalid_label=0)
    n = sum(1 for _ in it)
    assert n == 2


def test_ptb_perplexity_converges():
    """PTB-style LM convergence smoke (reference
    example/rnn/lstm_bucketing.py:96-107 trains with Perplexity): on a
    deterministic next-token corpus a small LSTM LM must push perplexity
    far below the uniform baseline (= vocab) within a short run — the
    interpretation anchor for the train_ptb_lstm bench row."""
    vocab, seq, batch, hidden = 50, 12, 8, 32
    rs = np.random.RandomState(0)
    # deterministic successor function: token t -> (3t + 1) % vocab
    starts = rs.randint(0, vocab, size=(64,))
    seqs = []
    for s in starts:
        row = [int(s)]
        for _ in range(seq):
            row.append((3 * row[-1] + 1) % vocab)
        seqs.append(row)
    X = np.array([r[:-1] for r in seqs], np.float32)
    Y = np.array([r[1:] for r in seqs], np.float32)

    data = mx.sym.Variable("data")
    embed = mx.sym.Embedding(data, input_dim=vocab, output_dim=hidden,
                             name="embed")
    cell = mx.rnn.LSTMCell(num_hidden=hidden, prefix="lstm_")
    outputs, _ = cell.unroll(seq, inputs=embed, merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
    label = mx.sym.Reshape(mx.sym.Variable("softmax_label"), shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, label, name="softmax")

    it = mx.io.NDArrayIter(X, Y, batch_size=batch, shuffle=True)
    mod = mx.mod.Module(net)
    metric = mx.metric.Perplexity(None)  # token 0 is a real label here
    mod.fit(it, eval_metric=metric, num_epoch=8,
            optimizer="adam", optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier())
    _name, ppl = metric.get()
    assert np.isfinite(ppl) and ppl < vocab / 5.0, ppl
