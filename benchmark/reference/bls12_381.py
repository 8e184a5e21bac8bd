"""Plain BLS12-381: the benchmark's own reference arithmetic.

Everything the benchmark checks the system against is computed here, in
straightforward Python integers, and imports nothing of the system under
test. It is slow on purpose: textbook formulas, affine Miller loop over
the untwisted points in Fp12, final exponentiation by plain powering.

Layout (drand's suite): public keys on G1 (48-byte compressed), signatures
on G2 (96-byte compressed, zcash flags), messages hashed to G2 with RFC
9380 ``BLS12381G2_XMD:SHA-256_SSWU_RO_``. The 3-isogeny of the SSWU map is
derived at import with Velu's formulas and pinned by the RFC 9380 J.10.1
test vector; import fails if the vector does not match.
"""

from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X = -0xD201000000010000  # the BLS parameter
assert R == X**4 - X**2 + 1 and P == ((X - 1) ** 2 // 3) * R + X

DST_G2 = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_NUL_"


# ------------------------------------------------------------------ fields

def fp_sqrt(a: int) -> int | None:
    a %= P
    r = pow(a, (P + 1) // 4, P)
    return r if r * r % P == a else None


class Fp:
    __slots__ = ("v",)

    def __init__(self, v: int = 0):
        self.v = v % P

    @staticmethod
    def zero():
        return Fp(0)

    @staticmethod
    def one():
        return Fp(1)

    def is_zero(self):
        return self.v == 0

    def __eq__(self, o):
        return isinstance(o, Fp) and self.v == o.v

    def __add__(self, o):
        return Fp(self.v + o.v)

    def __sub__(self, o):
        return Fp(self.v - o.v)

    def __neg__(self):
        return Fp(-self.v)

    def __mul__(self, o):
        return Fp(self.v * o.v)

    def mul_scalar(self, k: int):
        return Fp(self.v * k)

    def square(self):
        return Fp(self.v * self.v)

    def inverse(self):
        return Fp(pow(self.v, -1, P))

    def sqrt(self):
        r = fp_sqrt(self.v)
        return None if r is None else Fp(r)

    def to_bytes(self) -> bytes:
        return self.v.to_bytes(48, "big")

    @staticmethod
    def from_bytes(b: bytes):
        v = int.from_bytes(b, "big")
        if len(b) != 48 or v >= P:
            raise ValueError("Fp element not canonical")
        return Fp(v)


class Fp2:
    """c0 + c1*u, u^2 = -1."""

    __slots__ = ("c0", "c1")

    def __init__(self, c0: int = 0, c1: int = 0):
        self.c0 = c0 % P
        self.c1 = c1 % P

    @staticmethod
    def zero():
        return Fp2(0, 0)

    @staticmethod
    def one():
        return Fp2(1, 0)

    def is_zero(self):
        return self.c0 == 0 and self.c1 == 0

    def __eq__(self, o):
        return isinstance(o, Fp2) and self.c0 == o.c0 and self.c1 == o.c1

    def __add__(self, o):
        return Fp2(self.c0 + o.c0, self.c1 + o.c1)

    def __sub__(self, o):
        return Fp2(self.c0 - o.c0, self.c1 - o.c1)

    def __neg__(self):
        return Fp2(-self.c0, -self.c1)

    def __mul__(self, o):
        return Fp2(self.c0 * o.c0 - self.c1 * o.c1,
                   self.c0 * o.c1 + self.c1 * o.c0)

    def mul_scalar(self, k: int):
        return Fp2(self.c0 * k, self.c1 * k)

    def square(self):
        return self * self

    def conjugate(self):
        return Fp2(self.c0, -self.c1)

    def inverse(self):
        t = pow((self.c0 * self.c0 + self.c1 * self.c1) % P, -1, P)
        return Fp2(self.c0 * t, -self.c1 * t)

    def pow(self, e: int):
        out, base = Fp2.one(), self
        while e:
            if e & 1:
                out = out * base
            base = base.square()
            e >>= 1
        return out

    def sqrt(self):
        """p^2 = 9 mod 16: a candidate times a fourth root of unity."""
        if self.is_zero():
            return Fp2.zero()
        cand = self.pow((P * P + 7) // 16)
        for root in _FOURTH_ROOTS:
            r = cand * root
            if r.square() == self:
                return r
        return None

    def sgn0(self) -> int:
        return (self.c0 & 1) | ((self.c0 == 0) & (self.c1 & 1))

    def to_bytes(self) -> bytes:
        return self.c1.to_bytes(48, "big") + self.c0.to_bytes(48, "big")

    @staticmethod
    def from_bytes(b: bytes):
        if len(b) != 96:
            raise ValueError("Fp2 element must be 96 bytes")
        c1, c0 = int.from_bytes(b[:48], "big"), int.from_bytes(b[48:], "big")
        if c0 >= P or c1 >= P:
            raise ValueError("Fp2 element not canonical")
        return Fp2(c0, c1)


def _fourth_roots():
    half = pow(2, -1, P)
    a = fp_sqrt(half)
    if a is not None:
        s_u, s_mu = Fp2(a, a), Fp2(a, -a)
    else:
        a = fp_sqrt(-half)
        s_u, s_mu = Fp2(a, -a), Fp2(a, a)
    assert s_u.square() == Fp2(0, 1) and s_mu.square() == Fp2(0, -1)
    return [Fp2.one(), Fp2(0, 1), s_u, s_mu]


_FOURTH_ROOTS = _fourth_roots()
XI = Fp2(1, 1)  # Fp6 = Fp2[v]/(v^3 - XI)


class Fp6:
    __slots__ = ("c0", "c1", "c2")

    def __init__(self, c0, c1, c2):
        self.c0, self.c1, self.c2 = c0, c1, c2

    @staticmethod
    def zero():
        return Fp6(Fp2.zero(), Fp2.zero(), Fp2.zero())

    @staticmethod
    def one():
        return Fp6(Fp2.one(), Fp2.zero(), Fp2.zero())

    def __eq__(self, o):
        return self.c0 == o.c0 and self.c1 == o.c1 and self.c2 == o.c2

    def __add__(self, o):
        return Fp6(self.c0 + o.c0, self.c1 + o.c1, self.c2 + o.c2)

    def __sub__(self, o):
        return Fp6(self.c0 - o.c0, self.c1 - o.c1, self.c2 - o.c2)

    def __neg__(self):
        return Fp6(-self.c0, -self.c1, -self.c2)

    def __mul__(self, o):
        a0, a1, a2 = self.c0, self.c1, self.c2
        b0, b1, b2 = o.c0, o.c1, o.c2
        return Fp6(a0 * b0 + XI * (a1 * b2 + a2 * b1),
                   a0 * b1 + a1 * b0 + XI * (a2 * b2),
                   a0 * b2 + a1 * b1 + a2 * b0)

    def mul_by_v(self):
        return Fp6(XI * self.c2, self.c0, self.c1)

    def inverse(self):
        a0, a1, a2 = self.c0, self.c1, self.c2
        t0 = a0.square() - XI * (a1 * a2)
        t1 = XI * a2.square() - a0 * a1
        t2 = a1.square() - a0 * a2
        d = (a0 * t0 + XI * (a2 * t1) + XI * (a1 * t2)).inverse()
        return Fp6(t0 * d, t1 * d, t2 * d)


class Fp12:
    """c0 + c1*w, w^2 = v."""

    __slots__ = ("c0", "c1")

    def __init__(self, c0, c1):
        self.c0, self.c1 = c0, c1

    @staticmethod
    def one():
        return Fp12(Fp6.one(), Fp6.zero())

    @staticmethod
    def emb(a):
        """An Fp or Fp2 element as an Fp12 element."""
        a = a if isinstance(a, Fp2) else Fp2(a.v if isinstance(a, Fp) else a)
        return Fp12(Fp6(a, Fp2.zero(), Fp2.zero()), Fp6.zero())

    def __eq__(self, o):
        return self.c0 == o.c0 and self.c1 == o.c1

    def is_one(self):
        return self == Fp12.one()

    def __add__(self, o):
        return Fp12(self.c0 + o.c0, self.c1 + o.c1)

    def __sub__(self, o):
        return Fp12(self.c0 - o.c0, self.c1 - o.c1)

    def __mul__(self, o):
        a0, a1, b0, b1 = self.c0, self.c1, o.c0, o.c1
        return Fp12(a0 * b0 + (a1 * b1).mul_by_v(), a0 * b1 + a1 * b0)

    def square(self):
        return self * self

    def conjugate(self):
        return Fp12(self.c0, -self.c1)

    def inverse(self):
        d = (self.c0 * self.c0 - (self.c1 * self.c1).mul_by_v()).inverse()
        return Fp12(self.c0 * d, -(self.c1 * d))

    def pow(self, e: int):
        out, base = Fp12.one(), self
        while e:
            if e & 1:
                out = out * base
            base = base.square()
            e >>= 1
        return out

    def coeffs(self) -> list:
        """Fp2 coefficients of 1, w, w^2, w^3, w^4, w^5."""
        return [self.c0.c0, self.c1.c0, self.c0.c1, self.c1.c1, self.c0.c2,
                self.c1.c2]

    @staticmethod
    def from_coeffs(c):
        return Fp12(Fp6(c[0], c[2], c[4]), Fp6(c[1], c[3], c[5]))

    def frobenius2(self):
        """x -> x^(p^2): each w^i coefficient times XI^(i(p^2-1)/6)."""
        return Fp12.from_coeffs(
            [c * g for c, g in zip(self.coeffs(), _FROB2)])


_FROB2 = [XI.pow(i * (P * P - 1) // 6) for i in range(6)]
_probe = Fp12(Fp6(Fp2(3, 5), Fp2(7, 11), Fp2(13, 17)),
              Fp6(Fp2(19, 23), Fp2(29, 31), Fp2(37, 41)))
assert _probe.frobenius2() == _probe.pow(P * P)


# ------------------------------------------------------------------ curves

class _Point:
    """Jacobian point on y^2 = x^3 + B (Z = 0 is infinity)."""

    __slots__ = ("X", "Y", "Z")
    F = Fp
    B = None
    SIZE = 0

    def __init__(self, X, Y, Z):
        self.X, self.Y, self.Z = X, Y, Z

    @classmethod
    def infinity(cls):
        return cls(cls.F.one(), cls.F.one(), cls.F.zero())

    @classmethod
    def affine(cls, x, y):
        return cls(x, y, cls.F.one())

    @classmethod
    def generator(cls):
        return cls.affine(*cls.GEN)

    def is_infinity(self):
        return self.Z.is_zero()

    def __eq__(self, o):
        if self.is_infinity() or o.is_infinity():
            return self.is_infinity() and o.is_infinity()
        z1, z2 = self.Z.square(), o.Z.square()
        return (self.X * z2 == o.X * z1
                and self.Y * z2 * o.Z == o.Y * z1 * self.Z)

    def to_affine(self):
        zi = self.Z.inverse()
        return self.X * zi.square(), self.Y * zi.square() * zi

    def on_curve(self):
        x, y = self.to_affine()
        return y.square() == x.square() * x + self.B

    def double(self):
        if self.is_infinity():
            return self
        A, Bv = self.X.square(), self.Y.square()
        C = Bv.square()
        D = ((self.X + Bv).square() - A - C).mul_scalar(2)
        E = A.mul_scalar(3)
        X3 = E.square() - D.mul_scalar(2)
        return type(self)(X3, E * (D - X3) - C.mul_scalar(8),
                          (self.Y * self.Z).mul_scalar(2))

    def __add__(self, o):
        if self.is_infinity():
            return o
        if o.is_infinity():
            return self
        z1z1, z2z2 = self.Z.square(), o.Z.square()
        u1, u2 = self.X * z2z2, o.X * z1z1
        s1, s2 = self.Y * o.Z * z2z2, o.Y * self.Z * z1z1
        h = u2 - u1
        if h.is_zero():
            return self.double() if s1 == s2 else self.infinity()
        i = h.square().mul_scalar(4)
        j = h * i
        r = (s2 - s1).mul_scalar(2)
        v = u1 * i
        X3 = r.square() - j - v.mul_scalar(2)
        return type(self)(X3, r * (v - X3) - (s1 * j).mul_scalar(2),
                          ((self.Z + o.Z).square() - z1z1 - z2z2) * h)

    def __neg__(self):
        return type(self)(self.X, -self.Y, self.Z)

    def mul(self, k: int):
        if k < 0:
            return (-self).mul(-k)
        out = self.infinity()
        for bit in bin(k)[2:]:
            out = out.double()
            if bit == "1":
                out = out + self
        return out

    def to_bytes(self) -> bytes:
        if self.is_infinity():
            return bytes([0xC0]) + bytes(self.SIZE - 1)
        x, y = self.to_affine()
        out = bytearray(x.to_bytes())
        out[0] |= 0x80 | (0x20 if self._y_large(y) else 0)
        return bytes(out)

    @classmethod
    def from_bytes(cls, data: bytes):
        """Compressed zcash encoding, with the subgroup check."""
        if len(data) != cls.SIZE or not data[0] & 0x80:
            raise ValueError("not a compressed point")
        if data[0] & 0x40:
            if data[0] != 0xC0 or any(data[1:]):
                raise ValueError("bad infinity encoding")
            return cls.infinity()
        xb = bytearray(data)
        xb[0] &= 0x1F
        x = cls.F.from_bytes(bytes(xb))
        y = (x.square() * x + cls.B).sqrt()
        if y is None:
            raise ValueError("x not on curve")
        if cls._y_large(y) != bool(data[0] & 0x20):
            y = -y
        pt = cls.affine(x, y)
        if not pt.mul(R).is_infinity():
            raise ValueError("point not in the r-order subgroup")
        return pt


class G1(_Point):
    F = Fp
    B = Fp(4)
    SIZE = 48
    GEN = (Fp(0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB),
           Fp(0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1))

    @staticmethod
    def _y_large(y):
        return y.v > (P - 1) // 2


class G2(_Point):
    F = Fp2
    B = Fp2(4, 4)
    SIZE = 96
    GEN = (Fp2(0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
               0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E),
           Fp2(0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
               0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE))

    @staticmethod
    def _y_large(y):
        n = -y
        return (y.c1, y.c0) > (n.c1, n.c0)


assert G1.generator().mul(R).is_infinity()
assert G2.generator().mul(R).is_infinity()


# ----------------------------------------------------------------- pairing
# The twist y^2 = x^3 + 4(1+u) maps onto E(Fp12): y^2 = x^3 + 4 by
# (x, y) -> (x w^-2, y w^-3), since w^6 = 1+u.

_W_INV = Fp12(Fp6.zero(), Fp6.one()).inverse()
_W_INV2 = _W_INV * _W_INV
_W_INV3 = _W_INV2 * _W_INV
_gx, _gy = G2.GEN
assert ((Fp12.emb(_gy) * _W_INV3).square()
        == (Fp12.emb(_gx) * _W_INV2).pow(3) + Fp12.emb(4))


def _line(t, lam: Fp2, xp: Fp12, yp: Fp12) -> Fp12:
    """Line through the untwisted T with untwisted slope lam * w^-1,
    evaluated at P: y_P - y_T - lam' (x_P - x_T)."""
    xt, yt = Fp12.emb(t[0]) * _W_INV2, Fp12.emb(t[1]) * _W_INV3
    return yp - yt - Fp12.emb(lam) * _W_INV * (xp - xt)


def miller_loop(p: G1, q: G2) -> Fp12:
    """f_{|x|,Q}(P), conjugated because x < 0."""
    px, py = p.to_affine()
    xp, yp = Fp12.emb(px), Fp12.emb(py)
    qx, qy = q.to_affine()
    tx, ty = qx, qy
    f = Fp12.one()
    for bit in bin(-X)[3:]:
        lam = tx.square().mul_scalar(3) * ty.mul_scalar(2).inverse()
        f = f.square() * _line((tx, ty), lam, xp, yp)
        x3 = lam.square() - tx - tx
        tx, ty = x3, lam * (tx - x3) - ty
        if bit == "1":
            lam = (qy - ty) * (qx - tx).inverse()
            f = f * _line((tx, ty), lam, xp, yp)
            x3 = lam.square() - tx - qx
            tx, ty = x3, lam * (tx - x3) - ty
    return f.conjugate()


def final_exponentiation(f: Fp12) -> Fp12:
    """f^((p^12 - 1)/r): the easy part (p^6 - 1)(p^2 + 1), then one
    plain power by (p^4 - p^2 + 1)/r."""
    f = f.conjugate() * f.inverse()
    f = f.frobenius2() * f
    return f.pow((P**4 - P**2 + 1) // R)


def pairing(p: G1, q: G2) -> Fp12:
    if p.is_infinity() or q.is_infinity():
        return Fp12.one()
    return final_exponentiation(miller_loop(p, q))


# ------------------------------------------------------------ hash to G2

def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    ell = (n + 31) // 32
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\0"
                        + dst_prime).digest()
    bi = hashlib.sha256(b0 + b"\1" + dst_prime).digest()
    out = bi
    for i in range(2, ell + 1):
        bi = hashlib.sha256(bytes(a ^ b for a, b in zip(b0, bi))
                            + bytes([i]) + dst_prime).digest()
        out += bi
    return out[:n]


def hash_to_field(msg: bytes, dst: bytes) -> list:
    u = expand_message_xmd(msg, dst, 256)
    return [Fp2(int.from_bytes(u[i:i + 64], "big"),
                int.from_bytes(u[i + 64:i + 128], "big")) for i in (0, 128)]


# SSWU curve E': y^2 = x^3 + A x + B, 3-isogenous to the G2 twist
_A, _B, _Z = Fp2(0, 240), Fp2(1012, 1012), Fp2(-2, -1)


def _g_prime(x: Fp2) -> Fp2:
    return x.square() * x + _A * x + _B


def sswu(u: Fp2):
    zu2 = _Z * u.square()
    tv = zu2.square() + zu2
    if tv.is_zero():
        x1 = _B * (_Z * _A).inverse()
    else:
        x1 = -(_B * _A.inverse()) * (Fp2.one() + tv.inverse())
    y = _g_prime(x1).sqrt()
    x = x1
    if y is None:
        x = zu2 * x1
        y = _g_prime(x).sqrt()
    if u.sgn0() != y.sgn0():
        y = -y
    return x, y


def _poly_mod(a, m):
    a = list(a)
    inv = m[-1].inverse()
    while len(a) >= len(m):
        c = a[-1] * inv
        s = len(a) - len(m)
        for i, mi in enumerate(m):
            a[s + i] = a[s + i] - c * mi
        a.pop()
    while a and a[-1].is_zero():
        a.pop()
    return a


def _poly_mulmod(a, b, m):
    out = [Fp2.zero()] * (len(a) + len(b) - 1)
    for i, ai in enumerate(a):
        for j, bj in enumerate(b):
            out[i + j] = out[i + j] + ai * bj
    return _poly_mod(out, m)


def _poly_powmod(base, e, m):
    out, b = [Fp2.one()], _poly_mod(base, m)
    while e:
        if e & 1:
            out = _poly_mulmod(out, b, m)
        b = _poly_mulmod(b, b, m)
        e >>= 1
    return out


def _poly_gcd(a, b):
    while b:
        a, b = b, _poly_mod(a, b)
    inv = a[-1].inverse()
    return [c * inv for c in a]


def _roots(poly):
    """Distinct roots in Fp2 (gcd with x^(p^2) - x, then random splits)."""
    q = P * P
    d = _poly_powmod([Fp2.zero(), Fp2.one()], q, poly) + [Fp2.zero()] * 2
    d[1] = d[1] - Fp2.one()
    while d and d[-1].is_zero():
        d.pop()
    lin = _poly_gcd(poly, d)
    if len(lin) < 2:
        return []
    roots, todo, salt = [], [lin], 1
    while todo:
        f = todo.pop()
        if len(f) == 2:
            roots.append(-(f[0] * f[1].inverse()))
            continue
        while True:
            salt += 1
            h = _poly_powmod([Fp2(salt, salt % 7), Fp2.one()], (q - 1) // 2,
                             f) or [Fp2.zero()]
            h[0] = h[0] - Fp2.one()
            while h and h[-1].is_zero():
                h.pop()
            g = _poly_gcd(f, h) if h else f
            if 2 <= len(g) < len(f):
                todo.append(g)
                todo.append(_poly_div(f, g))
                break
    return roots


def _poly_div(a, b):
    a = list(a)
    out = [Fp2.zero()] * (len(a) - len(b) + 1)
    inv = b[-1].inverse()
    while len(a) >= len(b):
        c = a[-1] * inv
        s = len(a) - len(b)
        out[s] = c
        for i, bi in enumerate(b):
            a[s + i] = a[s + i] - c * bi
        a.pop()
    return out


def _cube_root(a: Fp2):
    q = P * P
    m, k = q - 1, 0
    while m % 3 == 0:
        m //= 3
        k += 1
    if a.pow((q - 1) // 3) != Fp2.one():
        return None
    c = a.pow(pow(3, -1, m))
    g = Fp2(2, 1)
    while g.pow((q - 1) // 3) == Fp2.one():
        g = g + Fp2(1, 1)
    z, zj = g.pow(m), Fp2.one()
    for _ in range(3**k):
        if (c * zj).pow(3) == a:
            return c * zj
        zj = zj * z
    return None


def _isogenies():
    """Every Velu 3-isogeny from E' onto the twist, as (x0, v, u, c2, c3):
    kernel x-coordinate, Velu sums, and the isomorphism (x, y) ->
    (c2 x, c3 y) that lands the codomain on y^2 = x^3 + 4(1+u)."""
    psi3 = [-_A.square(), _B.mul_scalar(12), _A.mul_scalar(6), Fp2.zero(),
            Fp2(3)]
    s3 = Fp2(-3).sqrt()
    w = (Fp2(-1) + s3) * Fp2(pow(2, -1, P))
    sixth = [Fp2.one(), -Fp2.one(), w, -w, w.square(), -w.square()]
    out = []
    for x0 in _roots(psi3):
        v = (x0.square().mul_scalar(3) + _A).mul_scalar(2)
        u = _g_prime(x0).mul_scalar(4)
        if not (_A - v.mul_scalar(5)).is_zero():
            continue
        ratio = Fp2(4, 4) * (_B - (u + x0 * v).mul_scalar(7)).inverse()
        s = ratio.sqrt()
        base = None
        for sign in (s, -s):
            c = _cube_root(sign)
            if c is not None and c.pow(6) == ratio:
                base = c
                break
        for zeta in sixth:
            c = base * zeta
            out.append((x0, v, u, c.square(), c.square() * c))
    return out


def _iso(params, x: Fp2, y: Fp2):
    x0, v, u, c2, c3 = params
    di = (x - x0).inverse()
    di2 = di.square()
    return (c2 * (x + v * di + u * di2),
            c3 * y * (Fp2.one() - v * di2 - (u + u) * di2 * di))


_H_EFF = int(
    "bc69f08f2ee75b3584c6a0ea91b352888e2a8e9145ad7689986ff031508ffe1329c2f178731db956d82"
    "bf015d1212b02ec0ec69d7477c1ae954cbc06689f6a359894c0adebbf6b4e8020005aaa95551", 16)

# RFC 9380 J.10.1: BLS12381G2_XMD:SHA-256_SSWU_RO_, msg = ""
_RFC_DST = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
_RFC_PX = Fp2(
    0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
    0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D)
_RFC_PY = Fp2(
    0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
    0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6)


def _map(params, u: Fp2) -> G2:
    return G2.affine(*_iso(params, *sswu(u)))


def _pin_isogeny():
    u0, u1 = hash_to_field(b"", _RFC_DST)
    for params in _isogenies():
        pt = (_map(params, u0) + _map(params, u1)).mul(_H_EFF)
        if not pt.is_infinity() and pt.to_affine() == (_RFC_PX, _RFC_PY):
            return params
    raise AssertionError("no derived isogeny reproduces RFC 9380 J.10.1")


_ISO = _pin_isogeny()


def hash_to_g2(msg: bytes, dst: bytes = DST_G2) -> G2:
    u0, u1 = hash_to_field(msg, dst)
    return (_map(_ISO, u0) + _map(_ISO, u1)).mul(_H_EFF)


# --------------------------------------------------------------------- BLS

def sign(sk: int, msg: bytes) -> bytes:
    return hash_to_g2(msg).mul(sk).to_bytes()


def verify(pub: G1, msg: bytes, sig: bytes) -> bool:
    """e(G1, sig) == e(pub, H(msg)); False for any malformed signature."""
    try:
        s = G2.from_bytes(sig)
    except ValueError:
        return False
    if s.is_infinity() or pub.is_infinity():
        return False
    f = miller_loop(-G1.generator(), s) * miller_loop(pub, hash_to_g2(msg))
    return final_exponentiation(f).is_one()
