"""BLS12-381 in plain Python integers: the benchmark's own arithmetic.

The benchmark signs its traffic and decides what every verdict should
be with this module alone. It imports nothing of the system under test,
so a change to the program cannot move the yardstick.

Points are Jacobian triples (X, Y, Z) with Z == 0 at infinity. G1
coordinates are ints mod P; G2 coordinates are pairs (c0, c1) meaning
c0 + c1*u in Fp2 = Fp[u]/(u^2 + 1). Hash-to-G2 is RFC 9380's
BLS12381G2_XMD:SHA-256_SSWU_RO_ with the Ethereum proof-of-possession
tag; compressed encodings are the ZCash format the consensus specs use.
"""

from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
BLS_X = -0xD201000000010000
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

G1_GEN = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
    1,
)
_HALF_P = (P - 1) // 2


# --- Fp2 ---------------------------------------------------------------------


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    t0 = a[0] * b[0]
    t1 = a[1] * b[1]
    return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)


def f2_sq(a):
    return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)


def f2_scale(a, k: int):
    return (a[0] * k % P, a[1] * k % P)


def f2_inv(a):
    n = pow(a[0] * a[0] + a[1] * a[1], P - 2, P)
    return (a[0] * n % P, -a[1] * n % P)


def f2_conj(a):
    return (a[0], -a[1] % P)


def f2_pow(a, e: int):
    out = (1, 0)
    for bit in bin(e)[2:]:
        out = f2_sq(out)
        if bit == "1":
            out = f2_mul(out, a)
    return out


def f2_is_zero(a) -> bool:
    return a[0] == 0 and a[1] == 0


def f2_sqrt(a):
    """A square root of `a`, or None (p = 3 mod 4: Adj and
    Rodriguez-Henriquez, Algorithm 9)."""
    if f2_is_zero(a):
        return (0, 0)
    a1 = f2_pow(a, (P - 3) // 4)
    alpha = f2_mul(f2_sq(a1), a)
    x0 = f2_mul(a1, a)
    if alpha == (P - 1, 0):
        x = (-x0[1] % P, x0[0])  # u * x0
    else:
        b = f2_pow(f2_add((1, 0), alpha), (P - 1) // 2)
        x = f2_mul(b, x0)
    return x if f2_sq(x) == a else None


def f2_sgn0(a) -> int:
    return (a[0] & 1) | (int(a[0] == 0) & a[1] & 1)


class _Fp:
    zero, one = 0, 1

    @staticmethod
    def add(a, b):
        return (a + b) % P

    @staticmethod
    def sub(a, b):
        return (a - b) % P

    @staticmethod
    def mul(a, b):
        return a * b % P

    @staticmethod
    def sq(a):
        return a * a % P

    @staticmethod
    def scale(a, k):
        return a * k % P

    @staticmethod
    def inv(a):
        return pow(a, P - 2, P)

    @staticmethod
    def is_zero(a):
        return a == 0


class _Fp2:
    zero, one = (0, 0), (1, 0)
    add, sub, mul, sq = (
        staticmethod(f2_add), staticmethod(f2_sub),
        staticmethod(f2_mul), staticmethod(f2_sq),
    )
    scale, inv, is_zero = (
        staticmethod(f2_scale), staticmethod(f2_inv), staticmethod(f2_is_zero),
    )


FP, FP2 = _Fp, _Fp2


# --- Jacobian group law, generic over the field (a = 0 curves) ---------------


def infinity(F):
    return (F.one, F.one, F.zero)


def is_inf(F, p) -> bool:
    return F.is_zero(p[2])


def dbl(F, p):
    X, Y, Z = p
    if F.is_zero(Z) or F.is_zero(Y):
        return infinity(F)
    A = F.sq(X)
    B = F.sq(Y)
    C = F.sq(B)
    D = F.scale(F.sub(F.sub(F.sq(F.add(X, B)), A), C), 2)
    E = F.scale(A, 3)
    X3 = F.sub(F.sq(E), F.scale(D, 2))
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), F.scale(C, 8))
    Z3 = F.scale(F.mul(Y, Z), 2)
    return (X3, Y3, Z3)


def add(F, p, q):
    if F.is_zero(p[2]):
        return q
    if F.is_zero(q[2]):
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = F.sq(Z1)
    Z2Z2 = F.sq(Z2)
    U1 = F.mul(X1, Z2Z2)
    U2 = F.mul(X2, Z1Z1)
    S1 = F.mul(F.mul(Y1, Z2), Z2Z2)
    S2 = F.mul(F.mul(Y2, Z1), Z1Z1)
    if U1 == U2:
        return dbl(F, p) if S1 == S2 else infinity(F)
    H = F.sub(U2, U1)
    I = F.sq(F.scale(H, 2))
    J = F.mul(H, I)
    r = F.scale(F.sub(S2, S1), 2)
    V = F.mul(U1, I)
    X3 = F.sub(F.sub(F.sq(r), J), F.scale(V, 2))
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), F.scale(F.mul(S1, J), 2))
    Z3 = F.mul(F.sub(F.sub(F.sq(F.add(Z1, Z2)), Z1Z1), Z2Z2), H)
    return (X3, Y3, Z3)


def neg(F, p):
    return (p[0], F.sub(F.zero, p[1]), p[2])


def mul(F, p, k: int):
    """[k]p by double-and-add over the bits of |k|."""
    if k < 0:
        p, k = neg(F, p), -k
    out = infinity(F)
    for bit in bin(k)[2:] if k else "":
        out = dbl(F, out)
        if bit == "1":
            out = add(F, out, p)
    return out


def affine(F, p):
    """(x, y) of a finite point, or None at infinity."""
    if F.is_zero(p[2]):
        return None
    zi = F.inv(p[2])
    zi2 = F.sq(zi)
    return (F.mul(p[0], zi2), F.mul(F.mul(p[1], zi2), zi))


def eq(F, p, q) -> bool:
    return affine(F, p) == affine(F, q)


def on_curve_g2(pt) -> bool:
    x, y = pt
    return f2_sq(y) == f2_add(f2_mul(f2_sq(x), x), (4, 4))


# --- G1 encoding -------------------------------------------------------------


def g1_compress(p) -> bytes:
    a = affine(FP, p)
    if a is None:
        return bytes([0xC0]) + bytes(47)
    out = bytearray(a[0].to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if a[1] > _HALF_P else 0)
    return bytes(out)


# --- hash to G2 (RFC 9380) ---------------------------------------------------


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    ell = (n + 31) // 32
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(
        bytes(64) + msg + n.to_bytes(2, "big") + b"\x00" + dst_prime
    ).digest()
    b = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        prev = bytes(x ^ y for x, y in zip(b0, b[-1]))
        b.append(hashlib.sha256(prev + bytes([i]) + dst_prime).digest())
    return b"".join(b)[:n]


def hash_to_field_fp2(msg: bytes, count: int = 2, dst: bytes = DST) -> list:
    uniform = expand_message_xmd(msg, dst, count * 128)
    return [
        tuple(
            int.from_bytes(uniform[64 * (j + 2 * i): 64 * (j + 2 * i + 1)], "big") % P
            for j in range(2)
        )
        for i in range(count)
    ]


_SSWU_A = (0, 240)
_SSWU_B = (1012, 1012)
_SSWU_Z = (P - 2, P - 1)

# 3-isogeny E2' -> E2 (RFC 9380 Appendix E.3), constant term first
_ISO_X_NUM = (
    (0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0, 0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1, 0),
)
_ISO_X_DEN = (
    (0, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63),
    (0xC, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F),
    (1, 0),
)
_ISO_Y_NUM = (
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0, 0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10, 0),
)
_ISO_Y_DEN = (
    (0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB),
    (0, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA9D3),
    (0x12, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA99),
    (1, 0),
)


def _horner(coeffs, x):
    acc = coeffs[-1]
    for c in reversed(coeffs[:-1]):
        acc = f2_add(f2_mul(acc, x), c)
    return acc


def _sswu(u):
    """Simplified SWU onto E2': y^2 = x^3 + A'x + B' (RFC 9380 6.6.2)."""
    zu2 = f2_mul(_SSWU_Z, f2_sq(u))
    tv1 = f2_add(f2_sq(zu2), zu2)
    if f2_is_zero(tv1):
        x1 = f2_mul(_SSWU_B, f2_inv(f2_mul(_SSWU_Z, _SSWU_A)))
    else:
        x1 = f2_mul(
            f2_mul(f2_neg(_SSWU_B), f2_inv(_SSWU_A)),
            f2_add(f2_inv(tv1), (1, 0)),
        )

    def g(x):
        return f2_add(f2_mul(f2_add(f2_sq(x), _SSWU_A), x), _SSWU_B)

    y = f2_sqrt(g(x1))
    x = x1
    if y is None:
        x = f2_mul(zu2, x1)
        y = f2_sqrt(g(x))
    if f2_sgn0(u) != f2_sgn0(y):
        y = f2_neg(y)
    return x, y


def _iso3(x, y):
    """The 3-isogeny onto E2, as a Jacobian point."""
    x_den = _horner(_ISO_X_DEN, x)
    y_den = _horner(_ISO_Y_DEN, x)
    if f2_is_zero(x_den) or f2_is_zero(y_den):
        return infinity(FP2)
    xa = f2_mul(_horner(_ISO_X_NUM, x), f2_inv(x_den))
    ya = f2_mul(f2_mul(y, _horner(_ISO_Y_NUM, x)), f2_inv(y_den))
    return (xa, ya, (1, 0))


_XI = (1, 1)
_PSI_CX = f2_inv(f2_pow(_XI, (P - 1) // 3))
_PSI_CY = f2_inv(f2_pow(_XI, (P - 1) // 2))


def psi(p):
    """untwist-Frobenius-twist on G2, on Jacobian coordinates."""
    X, Y, Z = p
    return (
        f2_mul(f2_conj(X), _PSI_CX), f2_mul(f2_conj(Y), _PSI_CY), f2_conj(Z)
    )


def clear_cofactor_g2(p):
    """[x^2 - x - 1]P + [x - 1]psi(P) + psi^2(2P) (Budroni-Pintore)."""
    x = BLS_X
    t0 = mul(FP2, p, x * x - x - 1)
    t1 = mul(FP2, psi(p), x - 1)
    t2 = psi(psi(dbl(FP2, p)))
    return add(FP2, add(FP2, t0, t1), t2)


def hash_to_g2(msg: bytes, dst: bytes = DST):
    """H(m) as a Jacobian G2 point."""
    u0, u1 = hash_to_field_fp2(bytes(msg), 2, dst)
    q = add(FP2, _iso3(*_sswu(u0)), _iso3(*_sswu(u1)))
    return clear_cofactor_g2(q)
