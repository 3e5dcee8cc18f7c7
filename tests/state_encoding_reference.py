"""The state store's records and the FinalizeBlockResponse written out
field by field from encoding/proto alone: the plain encoder that
tests/test_state_encode_once.py, tests/test_replay_loaded.py and
tests/test_fuzz_wire.py hold the program's to, byte for byte. It shares
no code with state/types.py, types/validator_set.py or abci/wire.py: it
walks `vs.validators` (rows with their priorities), grows one `bytes` and
keeps nothing."""

from cometbft_tpu.encoding import proto as pb

_KEY_FIELD = {
    "tendermint/PubKeyEd25519": 1,
    "tendermint/PubKeySecp256k1": 2,
    "tendermint/PubKeyBls12_381": 3,
}


def validator(v) -> bytes:
    key = pb.f_bytes(_KEY_FIELD[v.pub_key.type_tag()], v.pub_key.bytes(),
                     emit_empty=True)
    return (pb.f_bytes(1, v.address) + pb.f_embedded(2, key)
            + pb.f_varint(3, v.voting_power)
            + pb.f_varint(4, v.proposer_priority))


def validator_set(vs) -> bytes:
    out = b""
    for v in vs.validators:
        out += pb.f_embedded(1, validator(v))
    return out + pb.f_bytes(2, vs.get_proposer().address)


def params(cp) -> bytes:
    return (
        pb.f_embedded(1, pb.f_varint(1, cp.block.max_bytes)
                      + pb.f_varint(2, cp.block.max_gas))
        + pb.f_embedded(2, pb.f_varint(1, cp.evidence.max_age_num_blocks)
                        + pb.f_varint(2, cp.evidence.max_age_duration_ns)
                        + pb.f_varint(3, cp.evidence.max_bytes))
        + pb.f_embedded(3, b"".join(pb.f_string(1, t)
                                    for t in cp.validator.pub_key_types))
        + pb.f_embedded(4, pb.f_varint(
            1, cp.abci.vote_extensions_enable_height)))


def state(st) -> bytes:
    psh = st.last_block_id.part_set_header
    block_id = pb.f_bytes(1, st.last_block_id.hash) + pb.f_embedded(
        2, pb.f_varint(1, psh.total) + pb.f_bytes(2, psh.hash))
    out = (
        pb.f_string(1, st.chain_id)
        + pb.f_varint(2, st.initial_height)
        + pb.f_varint(3, st.last_block_height)
        + pb.f_embedded(4, block_id)
        + pb.f_embedded(5, pb.f_varint(1, st.last_block_time.seconds)
                        + pb.f_varint(2, st.last_block_time.nanos))
        + pb.f_varint(8, st.last_height_validators_changed)
        + pb.f_bytes(10, st.last_results_hash)
        + pb.f_bytes(11, st.app_hash)
        + pb.f_varint(12, st.last_height_params_changed)
        + pb.f_embedded(13, params(st.consensus_params))
    )
    for field, vs in ((6, st.validators), (7, st.last_validators),
                      (9, st.next_validators)):
        if vs is not None:
            out += pb.f_embedded(field, validator_set(vs))
    return out


def finalize_resp(r) -> bytes:
    out = b""
    for tr in r.tx_results:
        out += pb.f_embedded(
            1, pb.f_varint(1, tr.code) + pb.f_bytes(2, tr.data)
            + pb.f_string(3, tr.log) + pb.f_varint(5, tr.gas_wanted)
            + pb.f_varint(6, tr.gas_used))
    for vu in r.validator_updates:
        out += pb.f_embedded(
            2, pb.f_bytes(1, vu.pub_key_bytes)
            + pb.f_string(2, vu.pub_key_type) + pb.f_varint(3, vu.power))
    return out + pb.f_bytes(3, r.app_hash)
