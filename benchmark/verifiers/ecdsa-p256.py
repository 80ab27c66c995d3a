"""OpenSSL's verdict (through ``cryptography``) on a reply signed with
ECDSA-P256 over SHA-256: ``replica_pubs`` gives each replica's public
point (x, y), a signature is r || s, 32 bytes each, big-endian."""


class ReplyVerifier:
    def __init__(self, replica_pubs: dict):
        from cryptography.hazmat.primitives.asymmetric import ec

        self._keys = {
            rid: ec.EllipticCurvePublicNumbers(x, y, ec.SECP256R1()).public_key()
            for rid, (x, y) in replica_pubs.items()
        }

    def valid(self, replica_id: int, msg: bytes, signature: bytes) -> bool:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec, utils

        key = self._keys.get(replica_id)
        if key is None or len(signature) != 64:
            return False
        der = utils.encode_dss_signature(
            int.from_bytes(signature[:32], "big"), int.from_bytes(signature[32:], "big")
        )
        try:
            key.verify(der, msg, ec.ECDSA(hashes.SHA256()))
        except InvalidSignature:
            return False
        return True


def make(replica_pubs: dict):
    return ReplyVerifier(replica_pubs).valid
