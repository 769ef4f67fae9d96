"""The decode chunk (``serving.model.decode_chunk``: up to ``chunk`` decode
steps in a loop on the device) against single steps of the SAME jitted
program with the host between them — shared by the suites of the three
served blocks (test_serving.py, test_olmoe_serving.py,
test_phi4flash_serving.py). ``n`` is data, so a chunk of n steps and n
calls of one step run one executable: tokens, logits, the experts' loads
and every cache come out bit-equal, or the loop is at fault."""
import numpy as np


def lane(token, position, left, eos=-1):
    """One lane of a batch: its pending token, the tokens it has cached,
    the steps it may still take and its end-of-sequence id."""
    return dict(token=token, position=position, left=left, eos=eos)


def tables_for(lanes, nb, block_size):
    """A block table row a lane ``(B, nb)``: distinct blocks from 1 on,
    every position up to the lane's last write backed (a padded lane, no
    steps left: all trash)."""
    tables = np.zeros((len(lanes), nb), np.int32)
    nxt = 1
    for i, ln in enumerate(lanes):
        if not ln["left"]:
            continue
        need = min((ln["position"] + ln["left"] - 1) // block_size + 1, nb)
        tables[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    return tables


def chunk_equals_single_steps(program, max_len, lanes, caches, chunk):
    """Run ``lanes`` through one chunk and through single steps, assert
    they agree, return the chunk's ``(rows (chunk, B), loads or None)``.

    ``program(tokens, positions, context_lens, steps_left, eos, n, caches)
    -> (rows (chunk, B), logits (B, V), caches, loads (chunk, L, E) or
    None)`` is ``decode_chunk`` jitted over one batch's tables; ``caches``
    a dict of arrays whose axis 1 counts blocks or slots, index 0 the
    trash one (dead lanes write there: not compared)."""
    tok, pos, left, eos = (np.array([ln[k] for ln in lanes], np.int32)
                           for k in ("token", "position", "left", "eos"))
    n = int(min(chunk, left.max()))
    rows, logits, got, loads = program(tok, pos, pos + 1, left, eos, n,
                                       caches)
    rows, logits = np.asarray(rows), np.asarray(logits)

    want_rows = np.full_like(rows, -1)
    want_logits = np.zeros_like(logits)
    want = caches
    for j in range(n):
        alive = left > 0
        r, lg, want, ld = program(tok, pos, pos + 1, alive.astype(np.int32),
                                  eos, 1, want)
        r = np.asarray(r)[0]
        want_rows[j] = np.where(alive, r, -1)
        want_logits[alive] = np.asarray(lg)[alive]
        if loads is not None:
            np.testing.assert_array_equal(np.asarray(loads)[j],
                                          np.asarray(ld)[0])
        # a lane's last step: its steps used up, its EOS, the position cap
        ended = ((r == eos) & (eos >= 0)) | (pos + 1 >= max_len)
        tok = np.where(alive, r, tok)
        pos = pos + alive
        left = np.where(alive & ~ended, left - 1, 0)
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_array_equal(logits, want_logits)
    for name in caches:
        np.testing.assert_array_equal(
            np.asarray(got[name])[:, 1:], np.asarray(want[name])[:, 1:],
            err_msg=name)
    return rows, (None if loads is None else np.asarray(loads))
